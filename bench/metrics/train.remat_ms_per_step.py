"""Device time per traced training step of the forward that
``jax.checkpoint`` runs a second time in the backward: every operation
whose op_name holds ``rematted_computation``.  Moves
``train_tokens_per_s``."""
from bench import scopes


def read(run):
    return scopes.per_step(run, lambda a: a.remat)
