from repro.kernels.prefix_gather.kernel import pack_tables
from repro.kernels.prefix_gather.ops import (
    prefix_select_gather,
    prefix_select_gather_blocked,
)
from repro.kernels.prefix_gather.ref import prefix_select_ref

__all__ = ["pack_tables", "prefix_select_gather",
           "prefix_select_gather_blocked", "prefix_select_ref"]
