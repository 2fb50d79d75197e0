"""Reduction of a profiler trace to device busy time, idle share and the
time of named kernels.

On a TPU the device ops are the events of the "XLA Ops" line of each
`/device:TPU:<n>` plane; their names are HLO instruction names
(`%sweep_project.1 = f32[...] custom-call(...)`), reduced here to
`sweep_project.1`. Async copies live on another line and overlap the ops,
so they are not busy time. On the CPU, which the tests use, the ops are
the host events that carry an `hlo_op` stat. Times are seconds on the
profiler's clock, which host annotations share.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

_TPU_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_HLO_NAME = re.compile(r"^%?([^\s=]+)")


@dataclasses.dataclass(frozen=True)
class Op:
    name: str
    start: float
    dur: float
    device: int

    @property
    def end(self) -> float:
        return self.start + self.dur


def load(trace_dir: str):
    """The ProfileData of the one `.xplane.pb` a trace wrote under
    `trace_dir`."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise ValueError(f"expected one xplane file under {trace_dir}, "
                         f"found {files}")
    return ProfileData.from_file(files[0])


def hlo_name(event_name: str) -> str:
    m = _HLO_NAME.match(event_name)
    return m.group(1) if m else event_name


def base_name(name: str) -> str:
    """`sweep_project.12` -> `sweep_project`."""
    return re.sub(r"\.\d+$", "", name)


def device_ops(profile) -> list[Op]:
    ops = []
    tpu = False
    for plane in profile.planes:
        m = _TPU_PLANE.match(plane.name)
        if not m:
            continue
        tpu = True
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for e in line.events:
                ops.append(Op(hlo_name(e.name), e.start_ns * 1e-9,
                              e.duration_ns * 1e-9, int(m.group(1))))
    if not tpu:
        for plane in profile.planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                for e in line.events:
                    stats = dict(e.stats)
                    if "hlo_op" in stats:
                        ops.append(Op(hlo_name(e.name), e.start_ns * 1e-9,
                                      e.duration_ns * 1e-9,
                                      int(stats.get("device_ordinal", 0))))
    return sorted(ops, key=lambda o: (o.device, o.start))


def annotation(profile, name: str) -> tuple[float, float]:
    """(start, end) of the host annotation `name`; it must occur once."""
    found = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == name:
                    found.append((e.start_ns * 1e-9,
                                  (e.start_ns + e.duration_ns) * 1e-9))
    if len(found) != 1:
        raise ValueError(f"annotation {name!r} found {len(found)} times")
    return found[0]


def host_spans(profile, prefix: str) -> list[tuple[str, float, float]]:
    """(name, start, end) of every host annotation whose name starts with
    `prefix`."""
    out = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(prefix):
                    out.append((e.name, e.start_ns * 1e-9,
                                (e.start_ns + e.duration_ns) * 1e-9))
    return out


def clip(ops, t0: float, t1: float) -> list[Op]:
    """The ops inside [t0, t1], cut at its edges."""
    out = []
    for o in ops:
        s, e = max(o.start, t0), min(o.end, t1)
        if e > s:
            out.append(Op(o.name, s, e - s, o.device))
    return out


def union(intervals) -> list[tuple[float, float]]:
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1] = (merged[-1][0], max(merged[-1][1], e))
        else:
            merged.append((s, e))
    return merged


def busy_seconds(ops) -> float:
    """Union of the ops' intervals, averaged over the devices present."""
    devices = sorted({o.device for o in ops})
    if not devices:
        return 0.0
    total = 0.0
    for d in devices:
        total += sum(e - s for s, e in
                     union((o.start, o.end) for o in ops if o.device == d))
    return total / len(devices)


def matches(name: str, prefixes) -> bool:
    return any(base_name(name).startswith(p) for p in prefixes)


def seconds_of(ops, prefixes) -> float:
    """Device time of the ops whose names start with any of `prefixes`,
    as a union (ops of one device do not overlap on its op line), averaged
    over devices."""
    return busy_seconds([o for o in ops if matches(o.name, prefixes)])


def top_ops(ops, n: int = 10) -> list[list]:
    """The `n` op kinds that took most device time: [[name, seconds]]."""
    tot: dict[str, float] = {}
    for o in ops:
        key = base_name(o.name)
        tot[key] = tot.get(key, 0.0) + o.dur
    ndev = max(1, len({o.device for o in ops}))
    return [[k, v / ndev] for k, v in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(ops, t0: float, t1: float, spans, n: int = 10) -> list[list]:
    """The longest device-idle gaps of device 0 in [t0, t1], each named by
    the host span (name, start, end) that covers most of it."""
    busy = union((o.start, o.end) for o in ops if o.device == 0)
    gaps, cur = [], t0
    for s, e in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if t1 > cur:
        gaps.append((cur, t1))
    named = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        best, cover = "host:unspanned", 0.0
        for name, ss, se in spans:
            c = min(e, se) - max(s, ss)
            if c > cover:
                best, cover = name, c
        named.append([best, e - s])
    return named
