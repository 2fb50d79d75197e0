"""Pallas TPU kernels for the paper's projection hot-spots, order-generic.

tt_project / cp_project: batched dense-input (tensorized flat vector)
projections for ANY order N >= 2 — one launch per batch of buckets, JLT
scaling fused — via the mode-sweep kernels (_sweep.py).
tt_reconstruct / cp_reconstruct: the batched adjoint reconstructions.
struct: the compressed-domain subsystem — batched structured-input
(TT/CP-format) projections for all four (operator, input) pairings via
carry-sweep kernels (`struct.struct_project`, the paper's
O(k N d R R~ (R + R~)) path, any order 2..MAX_ORDER; replaces the retired
order-3-only `tt_dot`).
plan_contraction / ContractionPlan: the dense mode-sweep contraction
planner — kernel program + block-aligned, VMEM-budgeted tiles + grid for a
static order (KernelPlanError when nothing fits);
`struct.plan_carry_sweep` is its structured-input counterpart.
pick_tiles: the tile view of the planner, shared by all dense wrappers.
Validated in interpret mode against ref.py / struct/ref.py, and compiled
for a v5e chip by tests/test_tpu_compile.py.
"""
from . import ref, struct
from .fused_update import (fused_hbm_bytes, fused_update_buckets,
                           plan_fused_update, unfused_hbm_bytes)
from .ops import (MAX_ORDER, PIPELINES, ContractionPlan, KernelPlanError,
                  cp_project, cp_reconstruct, kernel_order_supported,
                  pick_tiles, plan_contraction, sweep_hbm_bytes,
                  tt_cores_squeezed, tt_project, tt_reconstruct)
from .struct import plan_carry_sweep, struct_hbm_bytes, struct_project

__all__ = ["MAX_ORDER", "PIPELINES", "ContractionPlan", "KernelPlanError",
           "cp_project",
           "cp_reconstruct", "fused_hbm_bytes", "fused_update_buckets",
           "kernel_order_supported", "pick_tiles", "plan_carry_sweep",
           "plan_contraction", "plan_fused_update", "ref", "struct",
           "struct_hbm_bytes", "struct_project", "sweep_hbm_bytes",
           "tt_cores_squeezed", "tt_project", "tt_reconstruct",
           "unfused_hbm_bytes"]
