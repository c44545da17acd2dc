"""served_rps: requests completed in the window over the window's length
(host clock). The window runs from its first request to its last reply."""


def read(run):
    w = run.window
    return w.completed / w.seconds if w.seconds > 0 else None
