"""A kernel's share of its roofline, in percent: the least time the chip could
take for the operations the algorithm requires (``count``, a function of the
family's file, per item) over the kernel's measured time. Compute bound: the
operations over the published bf16 peak."""

from benchmark.reductions import kernel_time


def reduce(spec, ctx):
    ps = kernel_time.time_ps(spec, ctx)
    count = getattr(ctx["family"], spec["count"], None)
    if not ps or count is None:
        return None
    # per chip: the kernel time is one chip's, so is its share of the step's items
    flops = count(ctx["cfg"]) * ctx["items_per_step"] / ctx["cell"]["chips"] * ctx["steps"]
    return 100.0 * (flops / ctx["peak"]["bf16_flops_per_s"]) / (ps * 1e-12)
