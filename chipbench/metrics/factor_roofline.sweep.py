"""Share of the roofline of the refactor program: the least time a v5e
needs for the useful work of K factorizations (``work.factor_work``: the
symbolic flops, A read and L + U written once) over its device time per
run in the traced window."""

from chipbench.work import factor_work, roofline_share

PROGRAM = "_refactor"


def read(ctx):
    if ctx.summary is None or not ctx.peaks:
        return None
    seconds, runs = ctx.summary.program(PROGRAM)
    if not runs or seconds <= 0:
        return None
    ops, nbytes = factor_work(ctx.work, ctx.counters["k"], ctx.factor_bytes,
                              ctx.values_bytes)
    share = roofline_share(ops, nbytes, seconds / runs, ctx.peaks)
    return None if share is None else share[0]
