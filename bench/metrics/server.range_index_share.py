"""server.range_index_share: masked σ evaluations served through a range
index over all masked σ evaluations in the window, in %: the program's
``range_index_selects`` (a σ evaluated over the rows a column's sorted
index selected) over ``range_index_selects`` + ``range_full_selects`` (a σ
evaluated over its base's full columns) (``relational/algebra.py``
``masked``, ``SERVER``, through ``ServingRuntime.metrics_snapshot()`` as
``server_*``, before and after). Nothing to read where the program counts
neither."""

COUNTERS = ("server_range_index_selects", "server_range_full_selects")


def snapshot(rt):
    snap = rt.metrics_snapshot()
    if not any(name in snap for name in COUNTERS):
        return {}
    return {name: snap.get(name, 0) for name in COUNTERS}


def read(run):
    index = run.delta("server_range_index_selects")
    full = run.delta("server_range_full_selects")
    if index is None or full is None or index + full <= 0:
        return None
    return 100.0 * index / (index + full)
