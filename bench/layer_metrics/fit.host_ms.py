"""Host time of a streaming-fit update: the mean duration of the
program's ``fit.update`` span, in milliseconds.  The update ends without
waiting for the device, so this is the host's part."""
from bench import spans


def read(ctx):
    return spans.mean_ms(ctx.spans, "fit.update")
