"""Share of the requests in the window that the decode-tile cache answered
from a tile it held: 1 - tile misses / requests answered (the service's
own miss counter; a request reads one tile), in percent."""


def read(ctx):
    done = ctx.stats.get("attempted", 0) - ctx.stats.get("failed", 0)
    if "tile_misses" not in ctx.stats or not done:
        return None
    return 100.0 * (1.0 - ctx.stats["tile_misses"] / done)
