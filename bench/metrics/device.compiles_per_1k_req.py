"""device.compiles_per_1k_req: XLA backend compiles and persistent-cache
loads in the window (a ``jax.monitoring`` listener the harness
registers), per 1,000 requests completed."""


def read(run):
    if not run.window.completed:
        return None
    return 1e3 * run.compiles / run.window.completed
