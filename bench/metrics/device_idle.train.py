"""Share of the traced training steps in which no operation ran on the
device (1 - union of operation intervals / window).  Moves
``train_tokens_per_s``."""


def read(run):
    return 100.0 * (1.0 - run.busy_s / run.window_s)
