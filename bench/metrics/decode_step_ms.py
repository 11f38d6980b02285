"""Model step, decode program: device time per decode-only step, over
the traced window (time with an operation on the device inside the
harness's span of each step)."""


def read(run):
    steps = [s for s in run.trace.steps() if s["kind"] == "decode"]
    if not steps:
        return None
    return sum(s["device_ns"] for s in steps) / len(steps) * 1e-6
