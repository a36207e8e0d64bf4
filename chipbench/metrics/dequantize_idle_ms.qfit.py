"""Device-idle milliseconds per fit inside the program's engine.dequantize
spans: the quantized finalize's capacity check (a sync on the device) and
the dequantization's dispatch.  The cell's runner reads the span from the
trace with its own (``program_spans.NAMES`` does not list it); a program
that opens no such span reads nothing."""

from chipbench import program_spans, readers

SPAN = "engine.dequantize"


def read(ctx, device_kind):
    w = readers.window(ctx)
    if w is None or not ctx.trace_data.ops:
        return None
    lo, hi = w
    chosen = [s for s in ctx.trace_data.spans
              if s.name == SPAN and s.start_ns >= lo and s.end_ns <= hi]
    if not chosen:
        return None
    return readers.per(program_spans.idle_ns(ctx, chosen) * 1e-9,
                       ctx.counts.get("fits"), 1e3)
