"""Device milliseconds of the factor per traced step: the module runs that
hold a ``hylu.factor.`` scope (``scopes.attribute``), whatever programs run
it, over the steps of the traced window."""

from chipbench.scopes import family_seconds


def read(ctx):
    seconds = family_seconds(ctx, "factor", "factor_ms.sweep")
    return None if seconds is None else 1e3 * seconds
