"""compiled.batch_share: batches served by the compiled tier over all
batches in the window, in % (``CompileManager.telemetry()``, before and
after). Nothing to read where the runtime has no compiled tier."""


def snapshot(rt):
    return dict(rt.compiler.telemetry()) if rt.compiler is not None else {}


def read(run):
    comp = run.delta("compiled_batches")
    interp = run.delta("interpreted_batches")
    if comp is None or interp is None or comp + interp <= 0:
        return None
    return 100.0 * comp / (comp + interp)
