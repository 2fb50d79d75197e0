"""What the metric files under `bench/metrics/` compute.

Each reader takes the run's context and returns a number, or None when
there is nothing to read; the harness then leaves the metric out of the
line. Every run's context has the window's outcome (`window`: attempted,
failed, window seconds, each completed request's latency) and `setup_s`;
a traced run's adds the device ops clipped to the window, busy and window
seconds by the trace, the harness's host spans, the system's counters
before and after the window, the projection work the window did, the
chip's peaks and the kernel names.
"""
from __future__ import annotations

import numpy as np

from bench import tracered


def per_call_ms(ctx):
    """A closed loop's time per call: all the window's time over all the
    calls it made."""
    w = ctx["window"]
    return 1e3 * w["window_s"] / w["attempted"] if w["attempted"] else None


def p95_ms(ctx):
    """An open loop's 95th percentile over every request due in the window,
    from due time to completion."""
    lat = ctx["window"].get("latency_s")
    return float(1e3 * np.percentile(lat, 95)) if lat is not None \
        and len(lat) else None


def kernel_seconds(ctx) -> float:
    return tracered.seconds_of(ctx["ops"], ctx["kernels"]["projection"])


def roofline(ctx):
    """Least time of the window's projection work on this chip, as a share
    of the device time of the projection kernels."""
    kt = kernel_seconds(ctx)
    if kt <= 0 or ctx["work"].flops <= 0:
        return None
    least, bound = ctx["work"].least_seconds(ctx["peaks"])
    ctx["notes"].append(
        f"roofline: {ctx['work'].flops:.6e} flop, {ctx['work'].bytes:.6e} B,"
        f" least {least:.6f} s ({bound}-bound) over kernel time {kt:.6f} s")
    return 100.0 * least / kt


def projection_share(ctx):
    busy = ctx["busy_s"]
    kt = kernel_seconds(ctx)
    return 100.0 * kt / busy if busy > 0 and kt > 0 else None


def idle_share(ctx):
    w = ctx["window_s"]
    return 100.0 * (1.0 - ctx["busy_s"] / w) if w > 0 and ctx["busy_s"] > 0 \
        else None


def mean_span_ms(ctx, name):
    d = ctx["spans"].get(name)
    return 1e3 * sum(d) / len(d) if d else None


def counter_delta(ctx, name):
    before, after = ctx["counters"][name]
    return float(after - before)
