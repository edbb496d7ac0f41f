"""Distance computations per query of the beam-search engine: the mean of
``with_stats`` ``dist_comps`` over the configuration's queries, from one
search at the cell's operating point and batch, outside the window."""


def read(ctx):
    return ctx.get("engine_dist_comps_mean")
