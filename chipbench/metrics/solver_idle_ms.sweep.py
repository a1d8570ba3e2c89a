"""Device idle milliseconds per traced step while one of the solver's
``hylu.`` host spans is open: the device idle time the solver's own host
path causes (``scopes.phases``)."""

from chipbench.scopes import phase


def read(ctx):
    return phase(ctx, "solver_idle_ms.sweep")
