"""device.idle_share: the share of the traced window in which no
operation ran on the device, in % (1 - union of device-op intervals over
the window, ``bench.tracing.reduce_trace``)."""


def read(run):
    dev = run.device
    if dev is None or dev.window_s <= 0 or dev.n_devices == 0:
        return None
    return 100.0 * dev.idle_share
