"""Misses of JAX's persistent compile cache in this run (the program's
``PersistentCacheStats``): 0 in every run after a checkout's first."""


def read(ctx):
    misses = ctx.cache.get("misses")
    return None if misses is None else float(misses)
