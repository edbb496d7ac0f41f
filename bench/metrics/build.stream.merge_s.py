"""Device seconds of the stream step's ``reservoir_merge`` scope in the
traced build: the segmented fold's second half, the per-row merge of the
chunk reservoir into the persistent one.  The trace's
``jit_stream_step`` ops are put down to their scopes by
``repro.tracing.op_scopes`` over the step's compiled text, taken after
the window (``benchlib.program_trace``)."""
from benchlib import program_trace


def read(ctx):
    return program_trace.stream_scope_seconds(ctx, "reservoir_merge")
