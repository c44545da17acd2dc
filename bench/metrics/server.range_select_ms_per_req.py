"""server.range_select_ms_per_req: wall time of the program's
``server.range_select`` spans (``relational/algebra.py`` ``masked``: the
search of a σ's two-sided integer bounds in the column's sorted index,
on the host) in the window, per request completed. A program without the
span reads nothing."""


def read(run):
    spans = run.spans("server.range_select")
    if not spans or not run.window.completed:
        return None
    return sum(s.wall_s for s in spans) * 1e3 / run.window.completed
