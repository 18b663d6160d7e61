"""Device time per traced training step under the ``mlp`` scope (dense MLP
or MoE), forward, recompute and backward.  Moves ``train_tokens_per_s``."""
from bench import scopes


def read(run):
    return scopes.per_step(run, lambda a: "mlp" in a.path)
