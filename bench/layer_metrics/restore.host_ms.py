"""Host time of a restore slab: the mean duration of the program's
``ckpt.restore_slab`` span (building the call and enqueueing the slab
program, which runs asynchronously), in milliseconds."""
from bench import spans


def read(ctx):
    return spans.mean_ms(ctx.spans, "ckpt.restore_slab")
