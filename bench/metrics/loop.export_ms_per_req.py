"""loop.export_ms_per_req: wall time of the program's ``loop.export`` spans
(``core/vectorize.py``: a columnar loop's answers turned into Python lists
and maps) in the window, per request completed."""


def read(run):
    spans = run.spans("loop.export")
    if not spans or not run.window.completed:
        return None
    return sum(s.wall_s for s in spans) * 1e3 / run.window.completed
