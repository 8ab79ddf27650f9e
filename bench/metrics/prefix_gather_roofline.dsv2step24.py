"""Share of its roofline that the prefix-gather kernel reaches in the
network cells, in %.

The kernel (``kernels/prefix_gather``; a network engine calls
``prefix_select_gather_blocked``, body ``_grouped_kernel``) is bound by
memory. Its bytes are counted from the work done, not from how launches
are grouped: every launch gathers for each of ``cells x chains`` designs
on each of the engine's ``layers``, and each such gather reads two int32
group-index rows of 8 slots and writes one 128-lane int32 row. Its
packed table blocks, copied in once per block, are left out, so the
share is a lower bound. Time: the kernel's device operations in the
traced window."""

KERNEL = ("prefix_select_gather", "_select_kernel", "_grouped_kernel")
INDEX_SLOTS = 8
LANES = 128
BYTES_PER_GATHER = 2 * INDEX_SLOTS * 4 + LANES * 4


def read(run):
    t = run.trace
    if t is None or run.peaks is None or not run.counters.get("layers"):
        return None

    def kernel(name, module):
        return any(k in name for k in KERNEL)

    launches = t.op_count(kernel)
    seconds = t.op_time_s(kernel)
    if not launches or seconds <= 0:
        return None
    c = run.counters
    gathers = launches * c["cells"] * c["chains"] * c["layers"]
    return (100.0 * gathers * BYTES_PER_GATHER
            / run.peaks["hbm_bytes_per_s"] / seconds)
