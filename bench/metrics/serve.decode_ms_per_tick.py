"""Device time of the decode sub-step (scope ``tick.decode``: the decode
half of a mixed tick, or the whole decode-only tick program) per engine
tick that dispatched a tick program in the traced window.  Moves
``itl_p95_ms``."""
from bench import scopes


def read(run):
    return scopes.per_tick(run, lambda a: a.tick == "tick.decode")
