"""optimizer.exists_unnested_share: existential checks answered
set-at-a-time over all existential checks in the window, in %: the
program's ``exists_set`` (the rows a semi-join probes, each one check)
over ``exists_set`` + ``exists_per_row`` (checks answered by a query of
their own, row at a time) (``relational/algebra.py`` ``SERVER``, through
``ServingRuntime.metrics_snapshot()`` as ``server_*``, before and after).
Nothing to read where the program counts no existential checks."""

COUNTERS = ("server_exists_set", "server_exists_per_row")


def snapshot(rt):
    snap = rt.metrics_snapshot()
    if not any(name in snap for name in COUNTERS):
        return {}
    return {name: snap.get(name, 0) for name in COUNTERS}


def read(run):
    unnested = run.delta("server_exists_set")
    per_row = run.delta("server_exists_per_row")
    if unnested is None or per_row is None or unnested + per_row <= 0:
        return None
    return 100.0 * unnested / (unnested + per_row)
