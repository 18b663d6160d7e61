"""Device time per traced training step of the attention layer: every
operation under an ``attn.*`` or ``nsa.*`` scope (projections, the three
NSA branches with their kernels, FSA's index lists, gates, output
projection), forward, recompute and backward.  Moves
``train_tokens_per_s``."""
from bench import scopes


def read(run):
    return scopes.per_step(run, lambda a: any(
        n.startswith(("attn.", "nsa.")) for n in a.path))
