"""Share of the roofline of the factor: the least time a v5e needs for the
useful work of one step's K factorizations (``work.factor_work``: the
symbolic flops, A read and L + U written once) over the factor's device
seconds per traced step (``scopes.family_seconds``, as
``factor_ms.sweep``)."""

from chipbench.scopes import family_seconds, nothing
from chipbench.work import factor_work, roofline_share


def read(ctx):
    if not ctx.peaks:
        return nothing("factor_roofline.sweep",
                       "the device is not in peaks.json")
    seconds = family_seconds(ctx, "factor", "factor_roofline.sweep")
    if seconds is None:
        return None
    ops, nbytes = factor_work(ctx.work, ctx.counters["k"], ctx.factor_bytes,
                              ctx.values_bytes)
    share = roofline_share(ops, nbytes, seconds, ctx.peaks)
    return None if share is None else share[0]
