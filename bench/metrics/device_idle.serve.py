"""Share of the traced serving window in which no operation ran on the
device (1 - union of operation intervals / window).  Moves
``itl_p95_ms``."""


def read(run):
    return 100.0 * (1.0 - run.busy_s / run.window_s)
