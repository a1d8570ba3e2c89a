"""Device milliseconds per run of the fused substitution + residual +
refinement program (``refined_batched_solver``, module
``jit_solve_refined``) in the traced window."""

PROGRAM = "solve_refined"


def read(ctx):
    if ctx.summary is None:
        return None
    seconds, runs = ctx.summary.program(PROGRAM)
    return 1e3 * seconds / runs if runs and seconds > 0 else None
