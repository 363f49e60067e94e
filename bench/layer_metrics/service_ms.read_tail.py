"""Service time of a read without queueing: the mean duration of the
program's ``decode_at`` span over the traced window, in milliseconds."""


def read(ctx):
    spans = [s.duration for s in ctx.spans if s.name == "decode_at"]
    if not spans:
        return None
    return 1e3 * sum(spans) / len(spans)
