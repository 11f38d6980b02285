"""Device (TPU v5e): share of the traced window with no operation
running on the device."""


def read(run):
    return 100.0 * (1.0 - run.trace.busy_s() / run.trace.window_s)
