"""Pallas TPU kernels for the compute hot-spots (validated via
``interpret=True`` on CPU; compiled path on TPU backends).

  systolic_gemm — BlockSpec-tiled GEMM carrying the paper's mapping knobs
                  (dataflow OS/WS/IS, split-K, tile shape).
  wkv6          — RWKV-6 data-dependent-decay recurrence.
  rglru         — RecurrentGemma gated linear recurrence.
  prefix_gather — prefix-table gather + per-chiplet-slot split-K select
                  (the device pathfinder's stage-3 inner loop).
"""
from repro.kernels.prefix_gather import (
    pack_tables,
    prefix_select_gather,
    prefix_select_ref,
)
from repro.kernels.rglru import rglru, rglru_assoc_ref, rglru_ref
from repro.kernels.systolic_gemm import gemm_ref, systolic_gemm
from repro.kernels.wkv6 import wkv6, wkv6_ref, wkv6_ref_vmapped

__all__ = [
    "systolic_gemm", "gemm_ref",
    "wkv6", "wkv6_ref", "wkv6_ref_vmapped",
    "rglru", "rglru_ref", "rglru_assoc_ref",
    "pack_tables", "prefix_select_gather", "prefix_select_ref",
]
