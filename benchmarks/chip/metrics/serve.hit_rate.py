"""The embedding cache's ``CacheStats.hit_rate`` over the window alone, as
a percentage (hits over lookups, all cache layers)."""


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    return 100.0 * ctx["window"]["hit_rate"]
