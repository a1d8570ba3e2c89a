"""Device milliseconds per traced step in the scope ``hylu.factor.panel``:
each unrolled level's width-1 pivot perturbation, bucketed panel LU and
per-node LU.  The union of the scope's operation intervals, so a loop and
its body count once (``scopes.phases``)."""

from chipbench.scopes import phase


def read(ctx):
    return phase(ctx, "factor_panel_ms.sweep")
