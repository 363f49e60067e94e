"""Dispatch of a streaming-fit update: the ``fit.dispatch`` spans (the
two uploads and the train-epoch call) under ``fit.update``, summed, over
the number of ``fit.update`` spans, in milliseconds."""
from bench import spans


def read(ctx):
    return spans.summed_per_root_ms(ctx.spans, "fit.update", "fit.dispatch")
