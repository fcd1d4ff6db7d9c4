"""Process start to the window's start: weights drawn on the device,
programs loaded or compiled, warm-up (host clock)."""


def read(run):
    return run.setup_s
