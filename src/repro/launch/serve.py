"""Serving driver — thin CLI + back-compat wrapper over ``repro.serving``.

The real engine lives in ``repro.serving.Engine``: paged NSA KV-cache,
continuous batching, variable-length prompts, per-slot positions, slot
recycling.  This module keeps the historical ``Engine``/``Request`` API
(fixed request list, greedy decode of N tokens) for existing callers and
adds a dense fallback loop for recurrent/encdec families whose state is not
paged KV.
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.configs import get_config, reduced
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build
from repro.serving import Engine as PagedEngine
from repro.serving import Request as ServeRequest
from repro.serving.engine import SUPPORTED_FAMILIES


@dataclasses.dataclass
class Request:
    """Back-compat request record (prompts may have different lengths)."""
    rid: int
    prompt: jnp.ndarray          # (S,) int32
    max_new: int = 16
    out: list = dataclasses.field(default_factory=list)


class Engine:
    """Back-compat facade: paged continuous batching for attention families,
    dense equal-length loop for recurrent/encdec families."""

    def __init__(self, cfg, batch_slots: int, max_len: int, mesh=None,
                 backend: str | None = None):
        self.cfg = cfg
        self.batch_slots = batch_slots
        self.max_len = max_len
        self.paged = cfg.family in SUPPORTED_FAMILIES
        if self.paged:
            # mesh= (("data","model") Mesh) routes to the sharded engine when
            # it spans >1 device; a 1x1 mesh is the plain engine
            self._eng = PagedEngine(cfg, n_slots=batch_slots, max_len=max_len,
                                    backend=backend, mesh=mesh)
        else:
            self.model = build(cfg)
            self.params = self.model.init(jax.random.PRNGKey(0))
            self.cache = self.model.init_cache(batch_slots, max_len)
            self._decode = jax.jit(self.model.decode_step)
            self._prefill = jax.jit(self.model.prefill)

    # ------------------------------------------------------------ paged
    def _run_paged(self, requests: list[Request], new_tokens: int) -> dict:
        t0 = time.time()
        serve_reqs = []
        for r in requests:
            sr = ServeRequest(prompt=np.asarray(r.prompt),
                              max_new=min(r.max_new, new_tokens))
            self._eng.scheduler.submit(sr)
            serve_reqs.append(sr)
        summary = self._eng.run()
        for r, sr in zip(requests, serve_reqs):
            r.out = list(sr.out)
        s = self._eng.stats
        return {"prefill_s": s["prefill_s"],
                "decode_s_per_token": s["decode_s"] / max(s["decode_ticks"], 1),
                "total_s": time.time() - t0,
                "page_util": summary["peak_page_util"],
                "outputs": [r.out for r in requests]}

    # ------------------------------------------------------------ dense
    def _run_dense(self, requests: list[Request], new_tokens: int) -> dict:
        """Equal-length dense loop (recurrent state is one row per slot, so
        variable-length admission needs per-slot state capture — tracked as
        an extension; the paged path above has no such restriction)."""
        lens = {int(np.asarray(r.prompt).shape[0]) for r in requests}
        if len(lens) != 1:
            raise NotImplementedError(
                f"family '{self.cfg.family}' serves equal-length batches only "
                f"(got prompt lengths {sorted(lens)})")
        if len(requests) != self.batch_slots:
            raise ValueError("dense fallback needs one request per slot")
        toks = jnp.stack([jnp.asarray(r.prompt) for r in requests])
        batch = {"tokens": toks, "labels": jnp.full_like(toks, -100)}
        if self.cfg.family == "encdec":
            batch["frames"] = jnp.zeros(
                (len(requests), self.cfg.enc_seq, self.cfg.d_model),
                jnp.dtype(self.cfg.dtype))
        t0 = time.time()
        logits, self.cache = self._prefill(self.params, self.cache, batch)
        pos = int(toks.shape[1])
        nxt = jnp.argmax(logits[:, :self.cfg.vocab], axis=-1).astype(jnp.int32)
        for r, t in zip(requests, list(nxt)):
            r.out.append(int(t))
        prefill_s = time.time() - t0
        t1 = time.time()
        for _ in range(new_tokens - 1):
            logits, self.cache = self._decode(
                self.params, self.cache, nxt,
                jnp.full((len(requests),), pos, jnp.int32))
            pos += 1
            nxt = jnp.argmax(logits[:, :self.cfg.vocab], axis=-1).astype(jnp.int32)
            for r, t in zip(requests, list(nxt)):
                if len(r.out) < min(r.max_new, new_tokens):
                    r.out.append(int(t))
        decode_s = time.time() - t1
        return {"prefill_s": prefill_s,
                "decode_s_per_token": decode_s / max(new_tokens - 1, 1),
                "total_s": time.time() - t0,
                "outputs": [r.out for r in requests]}

    def run(self, requests: list[Request], new_tokens: int) -> dict:
        if self.paged:
            return self._run_paged(requests, new_tokens)
        return self._run_dense(requests, new_tokens)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--slots", "--batch", dest="slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64,
                    help="max prompt length; mixed traffic draws 1/4..1x of it")
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--requests", type=int, default=0,
                    help="total requests (default: 2x slots)")
    ap.add_argument("--backend", default=None,
                    help="paged-decode backend (repro.attention registry "
                         "name, e.g. paged_kernel | paged_gather)")
    ap.add_argument("--mesh", default=None, metavar="DxM",
                    help="shard serving over a (data, model) mesh, e.g. 2x4 "
                         "(needs data*model devices; model must divide "
                         "n_kv_heads, data must divide --slots)")
    ap.add_argument("--reduced", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    mesh = None
    if args.mesh is not None:
        from repro.launch.mesh import make_mesh
        d, m = (int(x) for x in args.mesh.lower().split("x"))
        mesh = make_mesh((d, m), ("data", "model"))
    eng = Engine(cfg, args.slots, args.prompt_len + args.new_tokens + 8,
                 mesh=mesh, backend=args.backend)
    # dense fallback families decode one fixed batch: one request per slot
    n_req = (args.requests or 2 * args.slots) if eng.paged else args.slots
    rng = np.random.default_rng(0)
    reqs = []
    for i in range(n_req):
        plen = (args.prompt_len if not eng.paged
                else int(rng.integers(max(args.prompt_len // 4, 1),
                                      args.prompt_len + 1)))
        reqs.append(Request(i, jnp.asarray(
            rng.integers(0, cfg.vocab, size=(plen,)), jnp.int32),
            max_new=args.new_tokens))
    stats = eng.run(reqs, args.new_tokens)
    print(f"[serve] prefill {stats['prefill_s']*1e3:.1f}ms  "
          f"decode {stats['decode_s_per_token']*1e3:.1f}ms/token")
    if "page_util" in stats:
        print(f"[serve] peak page-pool utilization {stats['page_util']:.1%}")
    print(f"[serve] sample output: {stats['outputs'][0][:12]}")


if __name__ == "__main__":
    main()
