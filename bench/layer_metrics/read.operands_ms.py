"""Operand stacking of a served read: the ``nttd.operands`` spans (the
decode tile's operands built from the parameters) under ``decode_at``,
summed, over the number of ``decode_at`` spans, in milliseconds."""
from bench import spans


def read(ctx):
    return spans.summed_per_root_ms(ctx.spans, "decode_at", "nttd.operands")
