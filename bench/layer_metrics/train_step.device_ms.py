"""Device time of the train-epoch program (``jit_epoch``, one run per
``NTTDStreamFitter.update``), per run, in milliseconds."""


def read(ctx):
    runs = ctx.trace.program_count("jit_epoch")
    if not runs:
        return None
    return 1e3 * ctx.trace.program_seconds("jit_epoch") / runs
