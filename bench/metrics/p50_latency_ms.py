"""p50_latency_ms: median latency over every request of the window, from
its due time (open loop) or issue time (closed loop) to its reply (host
clock). A failed request counts as missing every limit."""

import numpy as np


def read(run):
    lat = np.asarray(run.window.latencies_s())
    if lat.size == 0:
        return None
    with np.errstate(invalid="ignore"):
        v = float(np.percentile(lat, 50))
    return v * 1e3 if np.isfinite(v) else None
