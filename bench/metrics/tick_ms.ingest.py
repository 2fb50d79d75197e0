"""tick_ms.ingest: mean wall time of SketchServer.tick (ms)."""
from bench.readers import mean_span_ms


def read(ctx):
    return mean_span_ms(ctx, "tick")
