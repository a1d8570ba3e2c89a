"""Mean refinement-loop iterations per step (``info["n_refine"]`` of
``solve_batched``: the loop count of the batch, after the base solve)."""


def read(ctx):
    its = ctx.counters.get("n_refine") or []
    return sum(its) / len(its) if its else None
