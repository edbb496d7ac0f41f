"""Final prune and entry-point links per build:
``timings["final_prune"] + timings["connect"]``, mean over the window's
builds."""


def read(ctx):
    builds = ctx.get("builds") or []
    if not builds:
        return None
    return sum(b["timings"]["final_prune"] + b["timings"]["connect"]
               for b in builds) / len(builds)
