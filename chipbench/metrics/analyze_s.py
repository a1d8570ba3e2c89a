"""Seconds the plan cache spent producing the analysis in set-up: a host
analyze on a miss, the load of the persisted plan on a disk hit
(``PlanCache.stats``: ``analyze_s`` + ``load_s``)."""


def read(ctx):
    return ctx.counters.get("analyze_s")
