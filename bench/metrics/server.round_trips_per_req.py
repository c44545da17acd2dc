"""server.round_trips_per_req: server round trips per request completed in
the window (``ExecutionResult.n_round_trips``): the queries that reached
``DatabaseServer.run`` rather than a cache."""


def read(run):
    w = run.window
    return w.round_trips / w.completed if w.completed else None
