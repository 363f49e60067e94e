"""The fold of a served read: the ``nttd.fold`` spans (upload of the
positions and the eager fold) under ``decode_at``, summed, over the number
of ``decode_at`` spans, in milliseconds."""
from bench import spans


def read(ctx):
    return spans.summed_per_root_ms(ctx.spans, "decode_at", "nttd.fold")
