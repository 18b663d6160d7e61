"""The one traffic generator: every mix is a data file under
``bench/traffic/`` whose parameters this module reads.

Serving (``"kind": "serve"``): an open loop.  Requests are due on a
schedule fixed before the run; the harness sends each at its due time
whether or not earlier ones have finished, and times it from that due time.
Training (``"kind": "train"``): one token batch per step, made on the
device from the seed.

Draws go through numpy's PCG64 (any seed below 2**64 is valid).  The
gaps between arrivals and the lengths come from a generator fixed by the
file alone; the seed draws the token ids (and the weights).  So every seed
sends the same work in the same pattern: with the handful of long requests
that a window holds at this system's speed, the order of arrivals alone
moved the median TTFT by 15% between seeds, against 1% between two runs
of one seed.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Req:
    due: float              # seconds after the window opens
    prompt: np.ndarray      # int32 token ids
    max_new: int


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % 2**64, stream])


def _lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """Draw ``n`` lengths from a spec: ``{"dist": "lognormal", "median",
    "sigma", "min", "max"}`` or ``{"dist": "uniform", "min", "max"}``
    (bounds inclusive)."""
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "lognormal":
        x = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
        return np.clip(np.rint(x), lo, hi).astype(np.int64)
    if spec["dist"] == "uniform":
        return rng.integers(lo, hi + 1, n)
    raise ValueError(f"unknown length distribution {spec['dist']!r}")


def arrivals(mix: dict, seconds: float, stream: int = 0) -> np.ndarray:
    """Due times in [0, seconds): Poisson at ``rate_per_s``, drawn by the
    file's fixed generator."""
    if mix.get("arrivals", "poisson") != "poisson":
        raise ValueError(f"unknown arrival process {mix['arrivals']!r}")
    rate = float(mix["rate_per_s"])
    due = np.cumsum(_rng(0, stream).exponential(
        1.0 / rate, int(rate * seconds * 3) + 16))
    return due[due < seconds]


def _segment(mix, seconds, seed, vocab, streams) -> list[Req]:
    due = arrivals(mix, seconds, streams[0])
    n = len(due)
    plen = _lengths(mix["prompt_len"], n, _rng(0, streams[1]))
    nout = _lengths(mix["output_len"], n, _rng(0, streams[2]))
    tok = _rng(seed, streams[3])
    return [Req(float(d), tok.integers(0, vocab, int(p), dtype=np.int32),
                int(o)) for d, p, o in zip(due, plen, nout)]


def serve_schedule(mix: dict, seconds: float, seed: int, vocab: int
                   ) -> list[Req]:
    """The requests of a run: a pre-roll of ``preroll_s`` that brings the
    engine to its steady state (due times below 0, not measured), then the
    requests due in the window [0, seconds)."""
    pre = float(mix.get("preroll_s", 0))
    head = [dataclasses.replace(r, due=r.due - pre)
            for r in _segment(mix, pre, seed, vocab, (10, 11, 12, 13))] \
        if pre > 0 else []
    return head + _segment(mix, seconds, seed, vocab, (0, 1, 2, 3))


def warmup_requests(mix: dict, n_slots: int, seed: int, vocab: int
                    ) -> list[Req]:
    """Requests that drive every program the window will use (the mixed
    tick, the decode tick, first-token reads) before it opens: one per slot
    and one more, so that a slot is refilled while others decode, at the
    mix's warm-up lengths (the programs' shapes do not depend on them)."""
    tok = _rng(seed, 4)
    plen, nout = int(mix["warmup_prompt_len"]), int(mix["warmup_output_len"])
    return [Req(0.0, tok.integers(0, vocab, plen, dtype=np.int32), nout)
            for _ in range(n_slots + 1)]


def train_batch_fn(mix: dict, vocab: int, seed: int):
    """-> jitted ``batch(step)`` making one step's {"tokens", "labels"} on
    the device: ``batch`` x ``seq_len`` ids uniform over the vocabulary,
    labels the next id.  Every step's rows differ."""
    import jax
    import jax.numpy as jnp

    b, n = int(mix["batch"]), int(mix["seq_len"])
    key = jax.random.key(0)
    key = jax.random.fold_in(jax.random.fold_in(key, seed % 2**31),
                             seed // 2**31)

    @jax.jit
    def batch(step):
        ids = jax.random.randint(jax.random.fold_in(key, step), (b, n + 1),
                                 0, vocab, jnp.int32)
        return {"tokens": ids[:, :-1], "labels": ids[:, 1:]}

    return batch
