"""Device seconds of the stream step's ``leaf_knn`` scope in the traced
build: each leaf's gather, distance GEMM, top-k and edge emission.  The
trace's ``jit_stream_step`` ops are put down to their scopes by
``repro.tracing.op_scopes`` over the step's compiled text, taken after
the window (``benchlib.program_trace``)."""
from benchlib import program_trace


def read(ctx):
    return program_trace.stream_scope_seconds(ctx, "leaf_knn")
