"""Device time per traced training step of FSA's index lists (scope
``nsa.index``: the normalized selection, the per-query-block union lists
of the forward and the per-KV-block query lists of the fused backward).
Moves ``train_tokens_per_s``."""
from bench import scopes


def read(run):
    return scopes.per_step(run, lambda a: a.scope == "nsa.index")
