"""Device time of token rows read through the page tables (scope
``kv.gather``: whole-view K/V gathers of the prefill, compressed rows,
compression windows) per engine tick that dispatched a tick program in the
traced window.  Moves ``itl_p95_ms``."""
from bench import scopes


def read(run):
    return scopes.per_tick(run, lambda a: a.scope == "kv.gather")
