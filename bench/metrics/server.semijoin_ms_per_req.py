"""server.semijoin_ms_per_req: wall time of the program's ``server.semijoin``
spans (``relational/algebra.py`` ``SemiJoin.execute_masked``: the key
columns and masks of both sides, the one jitted semi-join program, waited
for) in the window, per request completed."""


def read(run):
    spans = run.spans("server.semijoin")
    if not spans or not run.window.completed:
        return None
    return sum(s.wall_s for s in spans) * 1e3 / run.window.completed
