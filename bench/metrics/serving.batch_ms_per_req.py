"""serving.batch_ms_per_req: wall time of the program's ``batch`` spans
(``runtime/batch.py``) in the window, per request completed."""


def read(run):
    spans = run.spans("batch")
    if not spans or not run.window.completed:
        return None
    return sum(s.wall_s for s in spans) * 1e3 / run.window.completed
