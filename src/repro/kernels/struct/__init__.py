"""Compressed-domain sketching subsystem: batched structured-input (TT/CP)
projections via carry-sweep Pallas kernels.

The paper's headline efficiency claim is that f_TT(R)/f_CP(R) "can be
applied efficiently when the inputs are low rank tensors given in the CP
or TT format" — this package is that regime's hot path. All FOUR
(operator, input) pairings — TT x TT, TT x CP, CP x TT, CP x CP — share one
carry-sweep schedule at any order 2..MAX_ORDER, batched over the inputs in
ONE launch (replacing the retired order-3-only, unbatched `tt_dot`):

  plan.py  — `plan_carry_sweep` / `CarryPlan`: the carry program (one
             matmul-and-multiply step per mode) + block-aligned,
             VMEM-budgeted (tk, tb) tiles + the (k-outermost, batch) grid.
  carry.py — the Pallas kernel executing the program verbatim.
  ref.py   — order-generic batched einsum oracles (also the XLA path).
  ops.py   — `struct_project`: layout/padding/jit wrapper, single + batched.

Inputs arrive as `repro.core.formats` containers (`TTTensor` / `CPTensor`
or the batched `BatchedTTTensor` / `BatchedCPTensor`); `rp.project` routes
them here under the standard backend policy.
"""
from .ops import STRUCT_TYPES, struct_project, struct_rank
from .plan import CarryPlan, plan_carry_sweep, struct_hbm_bytes
from . import ref

__all__ = ["CarryPlan", "STRUCT_TYPES", "plan_carry_sweep", "ref",
           "struct_hbm_bytes", "struct_project", "struct_rank"]
