"""Share of the chip's peak that the whole read step reaches: entries
answered per second over the traced window, times the model FLOPs of an
entry, over the peak FLOP/s."""
from bench import flops


def read(ctx):
    entries = ctx.stats.get("entries", 0)
    if not entries:
        return None
    c = ctx.config
    rate = entries / ctx.stats["elapsed"]
    per_entry = flops.decode_flops_per_entry(c["d_prime"], c["hidden"], c["rank"])
    return 100.0 * rate * per_entry / ctx.peak["flops_per_s"]
