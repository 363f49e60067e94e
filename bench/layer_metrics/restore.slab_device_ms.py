"""Device time of a restore's slab program outside the decode tile (the
index build, the ``inv_pi`` gathers, the fold, the normalisation and the
write into the leaf's buffer), per slab program run, in milliseconds."""
from bench import checkpoints


def read(ctx):
    runs = ctx.trace.program_count("jit_restore_slab")
    if not runs:
        return None
    outside = ctx.trace.program_seconds("jit_restore_slab") - checkpoints.tile_seconds(ctx.trace)
    return 1e3 * outside / runs
