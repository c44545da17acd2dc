"""join_probe_roofline: the ``join_probe`` program's share of its roofline,
in %. Time: the device time of every event of the ``jit_join_probe`` XLA
program in the window's trace. Work: per call, the bytes the probe needs
whatever implements it (``bench.kernels.join_probe_bytes``, with the
shapes named in the configuration's ``kernel_shapes``), over the chip's
HBM bandwidth (``bench.peaks``). Nothing to read where the trace holds no
such program."""

from bench.kernels import join_probe_bytes, roofline_seconds

PROGRAM = "jit_join_probe"


def read(run):
    dev = run.device
    if dev is None or run.peaks is None:
        return None
    seconds = dev.program_s.get(PROGRAM, 0.0)
    calls = dev.program_calls.get(PROGRAM, 0)
    shapes = run.config.data.get("kernel_shapes", {}).get("join_probe")
    if seconds <= 0 or not calls or shapes is None:
        return None
    sizes = run.config.sizes
    nbytes = calls * join_probe_bytes(int(sizes[shapes["n_probe"]]),
                                      int(sizes[shapes["n_slots"]]))
    return 100.0 * roofline_seconds(0.0, nbytes, run.peaks) / seconds
