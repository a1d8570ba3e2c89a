"""Share of the roofline of the solve: the least time a v5e needs to read
L + U once per substitution and A once per residual, for every
substitution the traced steps ran (``work.solve_work``), over the solve's
device time in the traced window (``scopes.family_seconds`` times the
steps, as ``solve_ms.sweep``)."""

from chipbench.scopes import family_seconds, nothing
from chipbench.work import roofline_share, solve_work


def read(ctx):
    if not ctx.peaks:
        return nothing("solve_roofline.sweep",
                       "the device is not in peaks.json")
    per_step = family_seconds(ctx, "solve", "solve_roofline.sweep")
    if per_step is None:
        return None
    ops, nbytes = solve_work(ctx.work, sum(ctx.counters["substitutions"]),
                             ctx.factor_bytes, ctx.values_bytes)
    share = roofline_share(ops, nbytes, per_step * ctx.scoped.steps,
                           ctx.peaks)
    return None if share is None else share[0]
