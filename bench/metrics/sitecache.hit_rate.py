"""sitecache.hit_rate: site-cache hits over lookups in the window, in %
(``SiteCache.stats()``, ``runtime/sitecache.py``, before and after)."""


def snapshot(rt):
    return dict(rt.site_cache.stats())


def read(run):
    hits = run.delta("hits")
    misses = run.delta("misses")
    if hits is None or misses is None or hits + misses <= 0:
        return None
    return 100.0 * hits / (hits + misses)
