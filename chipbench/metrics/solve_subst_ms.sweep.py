"""Device milliseconds per traced step in the scope ``hylu.solve.subst``:
scale, permutations, L and U substitution.  The union of the scope's
operation intervals, so a loop and its body count once (``scopes.phases``)."""

from chipbench.scopes import phase


def read(ctx):
    return phase(ctx, "solve_subst_ms.sweep")
