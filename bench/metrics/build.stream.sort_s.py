"""Device seconds of the stream step's ``chunk_sort`` scope in the traced
build: the segmented fold's first half, one global sort of the chunk's
candidate edges into a chunk reservoir.  The trace's ``jit_stream_step``
ops are put down to their scopes by ``repro.tracing.op_scopes`` over the
step's compiled text, taken after the window
(``benchlib.program_trace``)."""
from benchlib import program_trace


def read(ctx):
    return program_trace.stream_scope_seconds(ctx, "chunk_sort")
