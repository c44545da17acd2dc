"""device.host_reads_per_req: blocking pulls of device arrays to the host
in the window, per request completed: the program's ``host_reads``
counters over every site (``repro.obs.transfer``, through
``ServingRuntime.metrics_snapshot()``), before and after. Nothing to read
where the program counts no transfers."""

from bench.transfers import snapshot  # noqa: F401  (the harness calls it)


def read(run):
    reads = run.delta("host_reads")
    if reads is None or not run.window.completed:
        return None
    return reads / run.window.completed
