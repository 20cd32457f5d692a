"""Share of the traced sub-window in which no operation ran on the device,
in percent: one minus the union of the device's operation intervals."""


def read(rec):
    tr = rec.trace
    if tr is None or not tr.busy:
        return None
    return 100.0 * (1.0 - tr.busy_s() / tr.window_s)
