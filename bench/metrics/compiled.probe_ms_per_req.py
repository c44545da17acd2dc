"""compiled.probe_ms_per_req: wall time of the program's ``compiled.probe``
spans (``compiled/exec.py``: keys to the device, ``join_probe``, positions
back, the host's remap) in the window, per request completed."""


def read(run):
    spans = run.spans("compiled.probe")
    if not spans or not run.window.completed:
        return None
    return sum(s.wall_s for s in spans) * 1e3 / run.window.completed
