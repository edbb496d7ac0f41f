"""Streaming leaf k-NN and HashPrune fold time per build:
``index.timings["build_leaves"]`` (ends in ``block_until_ready``), mean
over the window's builds."""


def read(ctx):
    builds = ctx.get("builds") or []
    if not builds:
        return None
    return sum(b["timings"]["build_leaves"] for b in builds) / len(builds)
