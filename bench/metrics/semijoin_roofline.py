"""semijoin_roofline: the semi-join program's share of its roofline, in %.
Time: the device time of every event of the jitted semi-join programs
(``relational/algebra.py``: ``jit__semijoin_probe``, the build of its
direct-address table ``jit__semijoin_build``, or ``jit__semijoin_sorted``)
in the window's trace. Work: the bytes a semi-join needs whatever
implements it (:func:`semijoin_bytes`) for the rows the program's
``semijoin_probe_rows`` and ``semijoin_build_rows`` counters add in the
window (a build side kept from an earlier call adds none) (through ``ServingRuntime.metrics_snapshot()``
as ``server_*``), over the chip's HBM bandwidth (``bench.peaks``).
Nothing to read where the trace holds no such program or the program
counts no semi-join rows."""

from bench.kernels import roofline_seconds

PROGRAMS = ("jit__semijoin_probe", "jit__semijoin_build",
            "jit__semijoin_sorted")
COUNTERS = ("server_semijoin_probe_rows", "server_semijoin_build_rows")
KEY_BYTES = 4       # int32 keys
RESULT_BYTES = 1    # one bool a probe row


def semijoin_bytes(probe_rows: float, build_rows: float) -> float:
    """Read each probe key and each build key once, write one result byte
    a probe key."""
    return (KEY_BYTES + RESULT_BYTES) * probe_rows + KEY_BYTES * build_rows


def snapshot(rt):
    snap = rt.metrics_snapshot()
    if not all(name in snap for name in COUNTERS):
        return {}
    return {name: snap[name] for name in COUNTERS}


def read(run):
    dev = run.device
    if dev is None or run.peaks is None:
        return None
    seconds = sum(dev.program_s.get(p, 0.0) for p in PROGRAMS)
    probe = run.delta("server_semijoin_probe_rows")
    build = run.delta("server_semijoin_build_rows")
    if seconds <= 0 or not probe or build is None:
        return None
    nbytes = semijoin_bytes(probe, build)
    return 100.0 * roofline_seconds(0.0, nbytes, run.peaks) / seconds
