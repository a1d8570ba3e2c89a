"""Share of the roofline of the fused solve program: the least time a v5e
needs to read L + U once per substitution and A once per residual, for
every substitution the traced steps ran (``work.solve_work``), over the
program's device time in the traced window."""

from chipbench.work import roofline_share, solve_work

PROGRAM = "solve_refined"


def read(ctx):
    if ctx.summary is None or not ctx.peaks:
        return None
    seconds, runs = ctx.summary.program(PROGRAM)
    subst = ctx.counters.get("substitutions") or []
    if not runs or seconds <= 0 or len(subst) != runs:
        return None
    ops, nbytes = solve_work(ctx.work, sum(subst), ctx.factor_bytes,
                             ctx.values_bytes)
    share = roofline_share(ops, nbytes, seconds, ctx.peaks)
    return None if share is None else share[0]
