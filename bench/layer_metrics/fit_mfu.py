"""Share of the chip's peak that the streaming fit reaches: entries
trained per second over the traced window (steps x batch of every update),
times the forward and backward FLOPs of an entry, over the peak FLOP/s."""
from bench import flops


def read(ctx):
    trained = ctx.stats.get("trained_entries", 0)
    if not trained:
        return None
    c = ctx.config
    rate = trained / ctx.stats["elapsed"]
    per_entry = flops.fit_flops_per_entry(c["d_prime"], c["hidden"], c["rank"])
    return 100.0 * rate * per_entry / ctx.peak["flops_per_s"]
