"""RBC partition time per build: ``index.timings["partition"]``, mean over
the window's builds (the program's own host-clock phase timing)."""


def read(ctx):
    builds = ctx.get("builds") or []
    if not builds:
        return None
    return sum(b["timings"]["partition"] for b in builds) / len(builds)
