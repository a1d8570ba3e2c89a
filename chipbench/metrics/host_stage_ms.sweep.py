"""Host milliseconds per traced step in the solver's ``hylu.stage`` spans:
staging A's values and the right-hand sides (``scopes.phases``)."""

from chipbench.scopes import phase


def read(ctx):
    return phase(ctx, "host_stage_ms.sweep")
