"""Model step, mixed prefill+decode program: device time per mixed step,
over the traced window."""


def read(run):
    steps = [s for s in run.trace.steps() if s["kind"] == "mixed"]
    if not steps:
        return None
    return sum(s["device_ns"] for s in steps) / len(steps) * 1e-6
