"""Device milliseconds per traced step in the scope ``hylu.factor.stage``: A
gathered and scaled, scattered into the factor's slots, the sentinel,
``amax``, the final slice.  The union of the scope's operation intervals,
so a loop and its body count once (``scopes.phases``)."""

from chipbench.scopes import phase


def read(ctx):
    return phase(ctx, "factor_stage_ms.sweep")
