"""Roofline share (%) of the f32 HBM gather-distance kernel: the least time
the chip needs for the distance computations the window's searches
report (``benchlib.roofline``), over the kernel's summed device time in
the trace, where the kernel's custom call is named after its wrapper."""
from benchlib import roofline

KERNEL = "gather_distance_hbm"


def read(ctx):
    tr, peak = ctx.get("trace"), ctx.get("peak")
    if tr is None or peak is None or ctx.get("packing") != "f32":
        return None
    return roofline.roofline_share(ctx["dist_comps"], ctx["d"], "f32",
                                   tr.kernel_seconds(KERNEL), peak)
