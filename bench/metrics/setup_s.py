"""setup_s: from the start of the run to the window: starting JAX, making
the data, building the tables and their statistics, compiling the
programs, warming up (host clock)."""


def read(run):
    return run.setup_s
