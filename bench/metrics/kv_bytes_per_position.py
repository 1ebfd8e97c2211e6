"""Device bytes of the KV pools per cached position, K and V over every
layer (``ServeStats.kv_pool_bytes / kv_pool_positions``, counted where the
scheduler builds the pools, padding included).  None where the program
records no such counters."""


def read(ctx):
    nbytes = getattr(ctx.stats, "kv_pool_bytes", 0)
    positions = getattr(ctx.stats, "kv_pool_positions", 0)
    if not nbytes or not positions:
        return None
    return nbytes / positions
