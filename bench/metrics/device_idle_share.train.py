"""device_idle_share.train: the share of the traced training window in which
no operation ran on the device, in percent (1 - busy / window).

The window runs from the start of the first traced call into the trainer
to the end of the last (``bench.tracing``).  Nothing to read: None.
"""


def read(ctx):
    red = ctx["reduced"]
    if red is None or red.window_s <= 0:
        return None
    return 100.0 * (1.0 - red.busy_s / red.window_s)
