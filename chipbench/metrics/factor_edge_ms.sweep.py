"""Device milliseconds per traced step in the scope ``hylu.factor.edge``: each
unrolled level's gathers, TRSM/GEMM and combined scatter-add.  The union of
the scope's operation intervals, so a loop and its body count once
(``scopes.phases``)."""

from chipbench.scopes import phase


def read(ctx):
    return phase(ctx, "factor_edge_ms.sweep")
