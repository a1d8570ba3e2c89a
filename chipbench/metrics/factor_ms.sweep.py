"""Device milliseconds per run of the batched refactor program
(``RepeatedSolveEngine.refactor_batched``, module ``jit__refactor``) in the
traced window."""

PROGRAM = "_refactor"


def read(ctx):
    if ctx.summary is None:
        return None
    seconds, runs = ctx.summary.program(PROGRAM)
    return 1e3 * seconds / runs if runs and seconds > 0 else None
