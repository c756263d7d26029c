"""A kernel's share of its roofline where memory bounds it, in percent: the
least time the chip could take to move the bytes the algorithm requires
(``count``, a function of the family's file, per item) at the published HBM
rate (``hbm_bytes_per_s`` of ``peaks.json``), over the kernel's measured time.
A count that holds only what must be read and written once cannot honestly
pass 100 %."""

from benchmark.reductions import kernel_time


def reduce(spec, ctx):
    ps = kernel_time.time_ps(spec, ctx)
    count = getattr(ctx["family"], spec["count"], None)
    if not ps or count is None:
        return None
    # per chip: the kernel time is one chip's, so is its share of the step's items
    moved = count(ctx["cfg"]) * ctx["items_per_step"] / ctx["cell"]["chips"] * ctx["steps"]
    return 100.0 * (moved / ctx["peak"]["hbm_bytes_per_s"]) / (ps * 1e-12)
