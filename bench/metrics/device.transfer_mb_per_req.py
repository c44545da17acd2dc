"""device.transfer_mb_per_req: bytes moved between host and device in the
window, both ways, in MB (1e6 bytes) per request completed: the program's
``h2d_bytes`` and ``d2h_bytes`` counters over every site
(``repro.obs.transfer``, through ``ServingRuntime.metrics_snapshot()``),
before and after. Nothing to read where the program counts no
transfers."""

from bench.transfers import snapshot  # noqa: F401  (the harness calls it)


def read(run):
    up, down = run.delta("h2d_bytes"), run.delta("d2h_bytes")
    if up is None or down is None or not run.window.completed:
        return None
    return (up + down) / 1e6 / run.window.completed
