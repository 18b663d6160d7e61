#!/usr/bin/env python3
"""Chip smoke test: the paged serving engine and the FSA kernels, compiled,
on TPU at the full published width of h2o-danube-3-4b (random weights made
from ``--seed``).

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # sharded engine vs single-chip engine

One chip, in order:
  1. device check: the first JAX device must be a TPU (no CPU fallback);
  2. ``Engine(cfg, n_slots=4, max_len=4096)`` is built and its decode and
     mixed-tick programs are compiled ahead of serving; both must contain
     the compiled Pallas kernel (``tpu_custom_call`` in the compiled HLO);
  3. serve: the engine answers 6 requests (prompts of 300..3000 tokens, 16
     new tokens each); every logit its tick programs return must be finite;
  4. at the first decode-only tick of that run, the tick's logits with
     ``paged_kernel`` vs ``paged_gather`` on the same engine state;
  5. ``nsa_attention(mode="train", backend="fsa")`` forward and fused
     backward vs the float32 ``reference`` at N=4096, at danube's and
     codeqwen1.5-7b's attention widths.

``--chips 4`` runs only the sharded path and its comparison: the single-chip
engine on the 6 requests, then ``ShardedEngine`` on a (data=1, model=4) mesh;
first-decode-step logits are compared and token agreement is reported.

Any failed check raises; the last stdout line, printed only on success, is
``{"ok": true, "device": {...}}``.  Timings are informational, not
benchmark metrics.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import numpy as np  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.attention import NSAConfig, nsa_attention  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core import apply_gates, init_nsa_params  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import transformer  # noqa: E402
from repro.serving import Engine  # noqa: E402

ARCH = "h2o-danube-3-4b"
N_SLOTS, MAX_LEN, MAX_NEW, N_REQUESTS = 4, 4096, 16, 6
PROMPT_RANGE = (300, 3000)        # >= min_seq_for_sparse: all NSA branches run
FSA_N = 4096
# attention widths (h, h_K, d, d_model) of the two configs the FSA check runs
FSA_WIDTHS = {"danube": (32, 8, 120, 3840), "codeqwen": (32, 32, 128, 4096)}

# Tolerances, fixed before any chip run.
#  * bf16 logits of two implementations of the same tick (gather vs kernel,
#    one chip vs four): max |a - b| over the rows compared, relative to
#    max |b|.  The residual stream is bf16, so one-ulp differences in an
#    attention output (2^-8 relative) propagate through every later layer.
LOGIT_TOL = 5e-2
#  * FSA vs the float32 reference, both with float32 inputs: relative L2
#    error ||a - b|| / ||b|| of out, dq, dk and dv.  The max abs error is
#    printed too; it is not bounded, because a near-tie in top-T block
#    selection may pick a different block for a single row.
FSA_TOL = 1e-2


def log(msg: str) -> None:
    print(msg, flush=True)


def _rel_max(a, b) -> float:
    return float(np.abs(a - b).max() / max(np.abs(b).max(), 1e-30))


# --------------------------------------------------------------- recording
class TickRecorder:
    """Wraps an engine's two tick programs without changing what they do.

    It checks that every logit they return is finite, keeps each request's
    first-decode-step logits on the host (keyed by submission index), and
    hands the operands of the first decode-only tick to ``on_first_decode``
    before the engine donates them.
    """

    def __init__(self, engine, on_first_decode=None):
        self.engine = engine
        self.programs = {"mixed": engine._mixed, "decode": engine._decode}
        self.index: dict[int, int] = {}         # rid -> submission index
        self.first_logits: dict[int, np.ndarray] = {}
        self.ticks = 0
        self._on_first_decode = on_first_decode
        engine._mixed = self._mixed
        engine._decode = self._decode

    def _record(self, dec_logits, decoding) -> None:
        vocab = self.engine.cfg.vocab
        for r in decoding:
            if len(r.out) == 1 and r.rid in self.index:
                self.first_logits[self.index[r.rid]] = np.asarray(
                    dec_logits[r.slot, :vocab], np.float32)

    def _check_finite(self, *logits) -> None:
        self.ticks += 1
        for x in logits:
            if not bool(jnp.isfinite(x).all()):
                raise AssertionError(f"non-finite logits at tick {self.ticks}")

    def _mixed(self, *args):
        eng = self.engine
        decoding = [r for r in eng.scheduler.active
                    if r.slot not in eng._pf_pos]
        pf_logits, dec_logits, data = self.programs["mixed"](*args)
        self._check_finite(pf_logits, dec_logits)
        self._record(dec_logits, decoding)
        return pf_logits, dec_logits, data

    def _decode(self, *args):
        if self._on_first_decode is not None:
            hook, self._on_first_decode = self._on_first_decode, None
            hook(args, [r.slot for r in self.engine.scheduler.active])
        logits, data = self.programs["decode"](*args)
        self._check_finite(logits)
        self._record(logits, self.engine.scheduler.active)
        return logits, data


def make_prompts(cfg, seed: int):
    rng = np.random.default_rng(seed)
    lens = np.linspace(*PROMPT_RANGE, N_REQUESTS).astype(int)
    return [rng.integers(0, cfg.vocab, size=(int(n),)).astype(np.int32)
            for n in lens]


def serve(engine, prompts, recorder):
    """Submit every prompt, drain the engine, check every request got
    ``MAX_NEW`` tokens.  Returns the token lists."""
    reqs = [engine.submit(p, max_new=MAX_NEW) for p in prompts]
    recorder.index = {r.rid: i for i, r in enumerate(reqs)}
    t0 = time.perf_counter()
    engine.run()
    wall = time.perf_counter() - t0
    outs = [list(r.out) for r in reqs]
    for i, o in enumerate(outs):
        if len(o) != MAX_NEW:
            raise AssertionError(f"request {i} returned {len(o)} tokens")
    ttft = sorted(r.first_token_t - r.submit_t for r in reqs)
    log(f"[serve] informational: {len(reqs)} requests, "
        f"{sum(map(len, outs))} tokens in {wall:.3f} s "
        f"({sum(map(len, outs)) / wall:.1f} tokens/s), "
        f"TTFT min/median/max {ttft[0]:.3f}/{ttft[len(ttft) // 2]:.3f}/"
        f"{ttft[-1]:.3f} s, decode tokens/s "
        f"{engine.summary()['decode_tokens_per_s']:.1f}")
    return outs


def compile_kernels_in(engine) -> None:
    """Phase 2: compile both tick programs ahead of serving, on operands
    shaped as the engine passes them; the compiled HLO must call the Pallas
    kernel (a Mosaic ``tpu_custom_call``), which rules out interpret mode.
    The engine's jit then reuses these executables."""
    b, c = engine.n_slots, engine.prefill_chunk
    zeros = lambda *s: jnp.zeros(s, jnp.int32)
    tables = engine.cache.views()
    decode_args = (engine.params, engine.cache.data, zeros(b), zeros(b),
                   tables)
    mixed_args = (engine.params, engine.cache.data, zeros(b, c), zeros(b),
                  zeros(b), zeros(b), zeros(b), jnp.zeros((b,), bool), tables)
    for name, fn, args in (("decode", engine._decode, decode_args),
                           ("mixed", engine._mixed, mixed_args)):
        t0 = time.perf_counter()
        text = fn.lower(*args).compile().as_text()
        log(f"[compile] informational: {name} tick program compiled in "
            f"{time.perf_counter() - t0:.1f} s")
        if "tpu_custom_call" not in text:
            raise AssertionError(f"{name} tick program has no tpu_custom_call")
        log(f"[compile] {name} tick program calls the compiled kernel "
            f"(tpu_custom_call x{text.count('tpu_custom_call')})")


def decode_backend_check(cfg, args, slots) -> None:
    """Phase 4: one decode tick's logits, Pallas kernel vs gather, on the
    same engine state (operands are not donated here)."""
    if not slots:
        raise AssertionError("decode-only tick with no active slot")
    logits = {}
    for backend in ("paged_kernel", "paged_gather"):
        policy = dataclasses.replace(cfg.nsa.policy, paged_backend=backend)
        c = dataclasses.replace(
            cfg, nsa=dataclasses.replace(cfg.nsa, policy=policy))
        fn = jax.jit(lambda p, d, t, pos, tb, c=c:
                     transformer.lm_paged_decode_step(p, d, t, pos, tb, c)[0])
        logits[backend] = np.asarray(fn(*args)[np.asarray(slots),
                                                 :cfg.vocab], np.float32)
    ker, ref = logits["paged_kernel"], logits["paged_gather"]
    if not (np.isfinite(ker).all() and np.isfinite(ref).all()):
        raise AssertionError("non-finite decode logits")
    err = _rel_max(ker, ref)
    agree = float((ker.argmax(-1) == ref.argmax(-1)).mean())
    log(f"[decode] paged_kernel vs paged_gather over {len(slots)} slots: "
        f"max abs {np.abs(ker - ref).max():.4g}, relative to max |logit| "
        f"{err:.4g} (tol {LOGIT_TOL}), argmax agreement {agree:.3f}")
    if err > LOGIT_TOL:
        raise AssertionError(f"decode logits differ: {err:.4g} > {LOGIT_TOL}")


# ------------------------------------------------------------------ phases
def run_one_chip(cfg, seed: int) -> None:
    prompts = make_prompts(cfg, seed)
    t0 = time.perf_counter()
    engine = Engine(cfg, n_slots=N_SLOTS, max_len=MAX_LEN, seed=seed)
    jax.block_until_ready(engine.params)
    log(f"[serve] informational: engine built (params from seed {seed}) in "
        f"{time.perf_counter() - t0:.1f} s")
    compile_kernels_in(engine)
    checked = []
    recorder = TickRecorder(engine, on_first_decode=lambda a, s: (
        decode_backend_check(cfg, a, s), checked.append(True)))
    serve(engine, prompts, recorder)
    log(f"[serve] {N_REQUESTS} requests x {MAX_NEW} tokens, all logits "
        f"finite over {recorder.ticks} ticks")
    if not checked:
        raise AssertionError("no decode-only tick: decode check did not run")
    del engine, recorder          # (a reference cycle: collect it now)
    gc.collect()
    for name, widths in FSA_WIDTHS.items():
        fsa_check(name, *widths, n=FSA_N, seed=seed)


def fsa_check(name, h, h_k, d, model_dim, *, n, seed) -> None:
    """Phase 5: FSA forward + fused backward vs the float32 reference.  The
    reference runs one KV-head group at a time (the heads are independent),
    which bounds its dense (N, g, N) score tensors."""
    nsa = NSAConfig()                   # paper defaults: B_K=64, T=16, B_Q=128
    g = h // h_k
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    p = init_nsa_params(ks[0], model_dim, h, d, nsa)
    gates = apply_gates(p, jax.random.normal(ks[1], (n, model_dim)))
    q = jax.random.normal(ks[2], (n, h, d))
    k = jax.random.normal(ks[3], (n, h_k, d))
    v = jax.random.normal(ks[4], (n, h_k, d))
    ct = jax.random.normal(ks[5], (n, h, d))

    def fwd_bwd(backend):
        def f(q, k, v, gates, ct):
            out, vjp = jax.vjp(lambda q, k, v: nsa_attention(
                p, gates, q, k, v, cfg=nsa, mode="train", backend=backend),
                q, k, v)
            return (out,) + vjp(ct)
        return jax.jit(f)

    with jax.default_matmul_precision("highest"):
        t0 = time.perf_counter()
        got = [np.asarray(x) for x in fwd_bwd("fsa")(q, k, v, gates, ct)]
        t_fsa = time.perf_counter() - t0
        ref_fn = fwd_bwd("reference")
        parts = []
        for j in range(h_k):
            hs, ksl = slice(j * g, (j + 1) * g), slice(j, j + 1)
            parts.append([np.asarray(x) for x in ref_fn(
                q[:, hs], k[:, ksl], v[:, ksl], gates[:, hs], ct[:, hs])])
    want = [np.concatenate(xs, axis=1) for xs in zip(*parts)]
    log(f"[fsa] informational: {name} fsa forward+backward (incl. compile) "
        f"{t_fsa:.1f} s")
    for label, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        if not np.isfinite(a).all():
            raise AssertionError(f"fsa {name} {label} is not finite")
        rel = float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))
        log(f"[fsa] {name} (h={h}, h_K={h_k}, d={d}, g={g}, N={n}) {label}: "
            f"max abs {np.abs(a - b).max():.4g}, relative L2 {rel:.4g} "
            f"(tol {FSA_TOL})")
        if rel > FSA_TOL:
            raise AssertionError(f"fsa {name} {label}: {rel:.4g} > {FSA_TOL}")


def run_four_chips(cfg, seed: int) -> None:
    """The single-chip engine, then ``ShardedEngine`` on a (1, 4) mesh, on
    the same requests and weights; first-decode-step logits compared."""
    from repro.launch.mesh import make_mesh

    prompts = make_prompts(cfg, seed)
    results = {}
    for label, mesh in (("single", None),
                        ("sharded", make_mesh((1, 4), ("data", "model")))):
        t0 = time.perf_counter()
        engine = Engine(cfg, n_slots=N_SLOTS, max_len=MAX_LEN, seed=seed,
                        mesh=mesh)
        jax.block_until_ready(engine.params)
        log(f"[{label}] informational: {type(engine).__name__} built in "
            f"{time.perf_counter() - t0:.1f} s")
        recorder = TickRecorder(engine)
        outs = serve(engine, prompts, recorder)
        results[label] = (outs, recorder.first_logits)
        log(f"[{label}] all logits finite over {recorder.ticks} ticks")
        del engine, recorder      # free the chip(s) before the next engine
        gc.collect()
    (out1, log1), (out4, log4) = results["single"], results["sharded"]
    compared = [i for i in range(N_REQUESTS)
                if out1[i][0] == out4[i][0] and i in log1 and i in log4]
    if not compared:
        raise AssertionError("no request with a common first token")
    errs = [_rel_max(log4[i], log1[i]) for i in compared]
    agree = np.mean([a == b for o1, o4 in zip(out1, out4)
                     for a, b in zip(o1, o4)])
    log(f"[sharded] first-decode-step logits, (1, 4) mesh vs one chip, over "
        f"{len(compared)} requests: max relative error {max(errs):.4g} "
        f"(tol {LOGIT_TOL}); token agreement {agree:.3f} "
        f"({N_REQUESTS} requests x {MAX_NEW} tokens)")
    if max(errs) > LOGIT_TOL:
        raise AssertionError(f"sharded logits differ: {max(errs):.4g}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    devices = jax.devices()
    dev = devices[0]
    log(f"[device] platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)}")
    if dev.platform != "tpu":
        print("chip_smoke: no TPU found; this check runs only on the chip",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices",
              file=sys.stderr)
        return 1
    log(f"[device] compile cache: {enable_compile_cache()}")

    cfg = get_config(ARCH)
    log(f"[config] {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.n_heads} q-heads / {cfg.n_kv_heads} KV heads, head_dim "
        f"{cfg.hd()}, d_ff {cfg.d_ff}, vocab {cfg.vocab}, {cfg.dtype}")
    if args.chips == 4:
        run_four_chips(cfg, args.seed)
    else:
        run_one_chip(cfg, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
