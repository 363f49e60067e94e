"""Host time of a served read: the mean over the program's ``decode_at``
spans of the duration less the ``payload.device_wait`` spans under it
(the host blocked on the device's answer), in milliseconds."""
from bench import spans


def read(ctx):
    return spans.mean_less_ms(ctx.spans, "decode_at", "payload.device_wait")
