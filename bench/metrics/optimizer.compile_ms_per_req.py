"""optimizer.compile_ms_per_req: wall time of the program's ``compile``
(plan search, ``api/session.py``) and ``lowering`` (compiled tier,
``compiled/manager.py``) spans in the window, per request completed.
Reads 0 where the tracer recorded spans and none of these fell in the
window."""


def read(run):
    if run.tracer is None or not run.window.completed:
        return None
    spans = run.spans("compile") + run.spans("lowering")
    return sum(s.wall_s for s in spans) * 1e3 / run.window.completed
