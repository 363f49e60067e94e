"""Share of its roofline that the decode tile reaches in a restore: the
least time the chip could take for the model FLOPs and bytes of the
entries restored in the traced window, summed per leaf at its own d'
(``flops.py``), over the device time of the decode tile inside the slab
programs.  FLOP-bound at these widths."""
from bench import checkpoints, flops


def read(ctx):
    seconds = checkpoints.tile_seconds(ctx.trace)
    restored = ctx.stats.get("restored") or {}
    if seconds <= 0 or not restored:
        return None
    c = ctx.config
    d_prime = {leaf["key"]: leaf["d_prime"] for leaf in c["leaves"]}
    work = sum(n * flops.decode_flops_per_entry(d_prime[k], c["hidden"], c["rank"])
               for k, n in restored.items())
    moved = sum(n * flops.decode_bytes_per_entry(d_prime[k]) for k, n in restored.items())
    least, _ = flops.roofline_seconds(work, moved, ctx.peak)
    return 100.0 * least / seconds
