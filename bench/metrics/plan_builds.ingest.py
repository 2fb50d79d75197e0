"""plan_builds.ingest: plan-cache misses inside the window."""
from bench.readers import counter_delta


def read(ctx):
    return counter_delta(ctx, "plan_builds")
