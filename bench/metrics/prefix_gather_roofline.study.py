"""Share of its roofline that the prefix-gather kernel reaches in the
study cells, in %.

The kernel (``kernels/prefix_gather``, body ``_select_kernel``) is bound
by memory: it does a few integer operations per byte. The bytes it must
move per launch, from its shapes (``prefix_gather_bytes``): the two
int32 group-index blocks it reads (8 slots per system) and the
``[systems, 128]`` int32 tile it writes, over the systems padded to its
128-system blocks. Its packed table, read once per launch, is left out,
so the share is a lower bound. Time: the kernel's device operations in
the traced window. One launch covers a whole ``[cells, chains]``
population."""

# the kernel's operation in the trace: the custom call is named after the
# jitted wrapper (``%vmap_jit_prefix_select_gather__.15 = s32[9216,128]
# custom-call(...)`` on a v5e), the body is ``_select_kernel``
KERNEL = ("prefix_select_gather", "_select_kernel")
BLOCK = 128
INDEX_SLOTS = 8
LANES = 128


def prefix_gather_bytes(systems: int) -> int:
    """HBM bytes one launch over ``systems`` designs reads and writes."""
    padded = -(-systems // BLOCK) * BLOCK
    return padded * (2 * INDEX_SLOTS * 4 + LANES * 4)


def read(run):
    t = run.trace
    if t is None or run.peaks is None:
        return None

    def kernel(name, module):
        return any(k in name for k in KERNEL)

    launches = t.op_count(kernel)
    seconds = t.op_time_s(kernel)
    if not launches or seconds <= 0:
        return None
    systems = run.counters["cells"] * run.counters["chains"]
    need = launches * prefix_gather_bytes(systems)
    return 100.0 * need / run.peaks["hbm_bytes_per_s"] / seconds
