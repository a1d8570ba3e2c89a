"""Device milliseconds per traced step in the scope ``hylu.factor.tail``: the
scan over the width-1 level chunks (0 where the plan has no scanned tail).
The union of the scope's operation intervals, so a loop and its body count
once (``scopes.phases``)."""

from chipbench.scopes import phase


def read(ctx):
    return phase(ctx, "factor_tail_ms.sweep")
