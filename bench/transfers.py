"""The program's host-device transfer counters, summed over their sites.

``ServingRuntime.metrics_snapshot()`` surfaces them as
``transfer_<counter>{site=...}`` (``repro.obs.transfer``): ``host_reads``,
``d2h_bytes`` and ``h2d_bytes``. :func:`snapshot` is the ``snapshot(rt)``
of the readers that read them; it returns nothing where the program counts
no transfers, so those readers then read nothing.
"""

COUNTERS = ("host_reads", "d2h_bytes", "h2d_bytes")
PREFIX = "transfer_"


def snapshot(rt):
    totals = {}
    for key, value in rt.metrics_snapshot().items():
        if key.startswith(PREFIX):
            name = key[len(PREFIX):].split("{", 1)[0]
            totals[name] = totals.get(name, 0) + value
    if not totals:
        return {}
    return {name: totals.get(name, 0) for name in COUNTERS}
