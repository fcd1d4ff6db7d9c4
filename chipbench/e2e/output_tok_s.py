"""Output tokens streamed to the client inside the window, over the
window's seconds (host clock, stream callbacks)."""
from benchlib.window import tokens_in_window


def read(run):
    return tokens_in_window(run) / run.seconds
