"""Share of the chip's peak that the whole restore step reaches: the model
FLOPs of the entries restored in the traced window, summed per leaf at
its own d' (``flops.py``), over the window's seconds and the peak
FLOP/s."""
from bench import flops


def read(ctx):
    restored = ctx.stats.get("restored") or {}
    if not restored:
        return None
    c = ctx.config
    d_prime = {leaf["key"]: leaf["d_prime"] for leaf in c["leaves"]}
    work = sum(n * flops.decode_flops_per_entry(d_prime[k], c["hidden"], c["rank"])
               for k, n in restored.items())
    return 100.0 * work / ctx.stats["elapsed"] / ctx.peak["flops_per_s"]
