"""Share of the traced window in which no operation ran on device 0: one
minus the union of its op intervals over the window."""


def read(run):
    t = run.trace
    if t is None or t.window_s <= 0 or not t.ops:
        return None
    return 1.0 - t.busy_per_device[0] / t.window_s
