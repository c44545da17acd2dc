"""client.index_reuse_rate: prefetch caches served from an index already
built over lookups in the window, in %: the program's ``client_index_reuses``
over ``client_index_builds`` + ``client_index_reuses``
(``relational/database.py`` ``ClientEnv.cache_by_column``, through
``ServingRuntime.metrics_snapshot()``, before and after). Nothing to read
where the program counts no index."""

COUNTERS = ("client_index_builds", "client_index_reuses")


def snapshot(rt):
    snap = rt.metrics_snapshot()
    return {name: snap.get(name, 0) for name in COUNTERS}


def read(run):
    builds = run.delta("client_index_builds")
    reuses = run.delta("client_index_reuses")
    if builds is None or reuses is None or builds + reuses <= 0:
        return None
    return 100.0 * reuses / (builds + reuses)
