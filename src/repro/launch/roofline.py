"""Three-term roofline analysis from a compiled (dry-run) executable.

  compute    = HLO_FLOPs / (chips * peak_FLOP/s)
  memory     = HLO_bytes_accessed / (chips * HBM_bw)
  collective = per-device link bytes / link_bw

HLO_FLOPs / bytes come from compiled.cost_analysis(). Collective bytes are
NOT in cost_analysis: we parse the post-partitioning HLO (compiled.as_text())
and apply a ring-algorithm traffic model per op type using the replica-group
size. Hardware constants come from `chip_peaks(device_kind)`, one table
keyed by the `device_kind` JAX reports; the dry-run targets a TPU v5e
production mesh (the `pod` axis crosses DCN; flagged separately). A device
kind missing from the table is an error, never a default.
"""
from __future__ import annotations

import dataclasses
import json
import re
from typing import Any



@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    """Published per-chip peaks of one accelerator kind."""

    flops: float                 # bf16 FLOP/s
    int8_ops: float              # int8 OP/s
    hbm_bw: float                # bytes/s
    hbm_bytes: float
    ici_link_bw: float           # bytes/s per inter-chip link
    source: str


# Keyed by `jax.devices()[0].device_kind`.
PEAKS = {
    "TPU v5 lite": ChipPeaks(
        flops=197e12, int8_ops=393e12, hbm_bw=819e9, hbm_bytes=16e9,
        ici_link_bw=50e9,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of "
               "inter-chip interconnect over four links"),
}
# The chip the dry-run's production mesh and the analytic kernel
# rooflines are sized for.
TARGET_DEVICE_KIND = "TPU v5 lite"


def chip_peaks(device_kind: str) -> ChipPeaks:
    """The peak table entry for `device_kind`; unknown kinds raise."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no peak table entry for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS)} — add the chip's published peaks with their "
            "source") from None

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1, "s4": 1, "u4": 1,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COLL_RE = re.compile(
    r"=\s+(?P<outtype>\([^)]*\)|[\w\[\],]+)(?:\{[\d,]*\})?\s+"
    r"(?P<op>all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(?P<start>-start)?\(")
_GROUPS_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=\[(\d+)\]")
_SOURCE_TARGET_RE = re.compile(r"source_target_pairs=\{")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(type_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def _group_size(line: str) -> int:
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return 1


def parse_collectives(hlo_text: str) -> dict[str, Any]:
    """Per-op-type totals + ring-model per-device link bytes."""
    per_type: dict[str, dict[str, float]] = {}
    link_bytes = 0.0
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if m is None:
            continue
        op = m.group("op")
        out_bytes = _shape_bytes(m.group("outtype"))
        n = _group_size(line)
        if op == "all-reduce":
            traffic = 2.0 * out_bytes * (n - 1) / max(n, 1)
        elif op == "all-gather":
            traffic = out_bytes * (n - 1) / max(n, 1)
        elif op == "reduce-scatter":
            traffic = out_bytes * (n - 1)            # input = out * n
        elif op == "all-to-all":
            traffic = out_bytes * (n - 1) / max(n, 1)
        else:  # collective-permute: one hop
            traffic = float(out_bytes)
        d = per_type.setdefault(op, {"count": 0, "bytes": 0.0, "traffic": 0.0})
        d["count"] += 1
        d["bytes"] += out_bytes
        d["traffic"] += traffic
        link_bytes += traffic
    return {"per_type": per_type, "link_bytes_per_device": link_bytes}


@dataclasses.dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    n_devices: int
    hlo_flops_per_device: float
    hlo_bytes_per_device: float
    collective: dict
    model_flops: float           # 6*N*D (active params) for the global step
    memory_per_device: dict
    device_kind: str = TARGET_DEVICE_KIND
    compute_s: float = 0.0
    memory_s: float = 0.0
    collective_s: float = 0.0
    bottleneck: str = ""
    useful_flops_frac: float = 0.0

    def finalize(self) -> "Roofline":
        peaks = chip_peaks(self.device_kind)
        self.compute_s = self.hlo_flops_per_device / peaks.flops
        self.memory_s = self.hlo_bytes_per_device / peaks.hbm_bw
        self.collective_s = (self.collective["link_bytes_per_device"]
                             / peaks.ici_link_bw)
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        self.bottleneck = max(terms, key=terms.get)
        total_hlo = self.hlo_flops_per_device * self.n_devices
        self.useful_flops_frac = (self.model_flops / total_hlo
                                  if total_hlo else 0.0)
        return self

    def to_json(self) -> dict:
        return dataclasses.asdict(self)


def memory_stats(compiled) -> dict[str, float]:
    mem: dict[str, float] = {}
    try:
        ma = compiled.memory_analysis()
        for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                     "temp_size_in_bytes", "alias_size_in_bytes",
                     "generated_code_size_in_bytes"):
            v = getattr(ma, attr, None)
            if v is not None:
                mem[attr] = float(v)
        mem["peak_bytes_per_device"] = (
            mem.get("argument_size_in_bytes", 0.0)
            + mem.get("output_size_in_bytes", 0.0)
            + mem.get("temp_size_in_bytes", 0.0)
            - mem.get("alias_size_in_bytes", 0.0))
    except Exception:  # pragma: no cover
        pass
    return mem


def _costs(compiled) -> tuple[float, float, dict]:
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    cost = dict(cost or {})
    coll = parse_collectives(compiled.as_text())
    return (float(cost.get("flops", 0.0)),
            float(cost.get("bytes accessed", 0.0)), coll)


def analyze_extrapolated(comp1, comp2, n1: float, n2: float, n_full: float,
                         *, arch: str, shape, mesh_name: str, n_devices: int,
                         cfg, memory: dict) -> "Roofline":
    """Linear-in-depth extrapolation from two shallow unrolled probes.

    cost(n) = a + b*n  (n = pattern instances); the full cell evaluates at
    n_full. Exact for flops/bytes; collectives are per-type linear too.
    """
    f1, b1, c1 = _costs(comp1)
    f2, b2, c2 = _costs(comp2)

    def extrap(v1, v2):
        slope = (v2 - v1) / (n2 - n1)
        return max(v1 + slope * (n_full - n1), 0.0)

    per_type: dict[str, dict[str, float]] = {}
    for op in set(c1["per_type"]) | set(c2["per_type"]):
        d1 = c1["per_type"].get(op, {"count": 0, "bytes": 0.0, "traffic": 0.0})
        d2 = c2["per_type"].get(op, {"count": 0, "bytes": 0.0, "traffic": 0.0})
        per_type[op] = {k: extrap(d1[k], d2[k]) for k in
                        ("count", "bytes", "traffic")}
    coll = {"per_type": per_type,
            "link_bytes_per_device": extrap(c1["link_bytes_per_device"],
                                            c2["link_bytes_per_device"]),
            "probe_instances": [n1, n2, n_full]}
    return Roofline(
        arch=arch, shape=shape.name, mesh=mesh_name, n_devices=n_devices,
        hlo_flops_per_device=extrap(f1, f2),
        hlo_bytes_per_device=extrap(b1, b2),
        collective=coll, model_flops=model_flops_for(cfg, shape),
        memory_per_device=memory,
    ).finalize()


def model_flops_for(cfg, shape) -> float:
    """MODEL_FLOPS = 6*N_active*D_tokens (train) or 2*N_active*D (inference)."""
    n_active = cfg.active_param_count()
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    mult = 6 if shape.kind == "train" else 2
    return float(mult) * n_active * tokens


def analyze(compiled, *, arch: str, shape, mesh_name: str, n_devices: int,
            cfg) -> Roofline:
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    cost = dict(cost or {})
    flops = float(cost.get("flops", 0.0))
    byts = float(cost.get("bytes accessed", 0.0))
    mem: dict[str, float] = {}
    try:
        ma = compiled.memory_analysis()
        for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                     "temp_size_in_bytes", "alias_size_in_bytes",
                     "generated_code_size_in_bytes"):
            v = getattr(ma, attr, None)
            if v is not None:
                mem[attr] = float(v)
    except Exception as e:  # pragma: no cover
        mem["error"] = 0.0
    coll = parse_collectives(compiled.as_text())
    return Roofline(
        arch=arch, shape=shape.name, mesh=mesh_name, n_devices=n_devices,
        hlo_flops_per_device=flops, hlo_bytes_per_device=byts,
        collective=coll, model_flops=model_flops_for(cfg, shape),
        memory_per_device=mem,
    ).finalize()
