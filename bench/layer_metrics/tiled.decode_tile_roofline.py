"""Share of its roofline that the decode tile reaches behind the tile
cache: the least time the chip could take for the model FLOPs and bytes
of the entries the misses decoded in the traced window (a whole tile a
miss, ``flops.py``), over the summed device time of the decode-tile
program.  Hits decode nothing and are not counted."""
from bench import flops


def read(ctx):
    seconds = ctx.trace.program_seconds("decode_tile")
    decoded = ctx.stats.get("decoded_entries", 0)
    if seconds <= 0 or not decoded:
        return None
    c = ctx.config
    work = decoded * flops.decode_flops_per_entry(c["d_prime"], c["hidden"], c["rank"])
    moved = decoded * flops.decode_bytes_per_entry(c["d_prime"])
    least, _ = flops.roofline_seconds(work, moved, ctx.peak)
    return 100.0 * least / seconds
