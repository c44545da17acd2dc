"""client.lookup_ms_per_req: wall time of the program's ``client.lookup``
spans (``relational/database.py``: one prefetch-cache lookup and the rows
it reads) in the window, per request completed."""


def read(run):
    spans = run.spans("client.lookup")
    if not spans or not run.window.completed:
        return None
    return sum(s.wall_s for s in spans) * 1e3 / run.window.completed
