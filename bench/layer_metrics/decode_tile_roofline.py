"""Share of its roofline that the decode tile reaches: the least time the
chip could take for the model FLOPs and bytes of the entries decoded in
the traced window (``flops.py``), over the summed device time of the
decode-tile program (``jit_decode_tile``).  The FLOPs bound it at the
configurations' widths."""
from bench import flops


def read(ctx):
    seconds = ctx.trace.program_seconds("decode_tile")
    entries = ctx.stats.get("entries", 0)
    if seconds <= 0 or not entries:
        return None
    c = ctx.config
    work = entries * flops.decode_flops_per_entry(c["d_prime"], c["hidden"], c["rank"])
    moved = entries * flops.decode_bytes_per_entry(c["d_prime"])
    least, _ = flops.roofline_seconds(work, moved, ctx.peak)
    return 100.0 * least / seconds
