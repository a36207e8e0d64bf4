"""Host milliseconds per fit that JAX spends tracing, lowering and compiling
(persistent-cache loads included): the trace_ms + lower_ms + compile_ms that
the program's spans carry, summed over the spans in the window."""

from chipbench import program_spans


def read(ctx, device_kind):
    return program_spans.compile_ms_per_fit(ctx)
