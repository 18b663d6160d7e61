"""Device time of the prefill sub-step (scope ``tick.prefill``) per mixed
tick: summed over the ``engine.tick`` spans of the traced window that hold
an ``engine.prefill_chunk`` span, over their number.  Moves
``itl_p95_ms``."""
from bench import scopes


def read(run):
    return scopes.per_tick(run, lambda a: a.tick == "tick.prefill",
                           mixed_only=True)
