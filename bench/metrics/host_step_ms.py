"""Mean wall time of one ``Engine.step()`` call in the window, on the
harness's clock. It includes any wait the step makes on the device."""


def read(rec):
    return sum(rec.step_ms) / len(rec.step_ms) if rec.step_ms else None
