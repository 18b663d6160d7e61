"""Serving cells: the program's ``Engine`` under an open loop.

Set-up makes the weights from the seed, builds the engine and drives a
warm-up through every program the window uses.  The window then sends each
request of the schedule at its due time and steps the engine whenever it
has work; the harness stamps every delivered token in ``on_token``.  A
pre-roll before the window brings the engine to its steady state.  After
the window closes no request is sent; the engine runs on until every
request due in the window has finished (at most ``drain_s``).

Correctness: a sample of the finished requests, drawn from the seed with
the longest among them, is run through ``bench.reference`` once the engine
is freed; every served token is judged by how far its reference logit lies
below the reference's best at that position.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from bench import latency, reference, traffic, weights
from bench.spec import arch_of, program_config


class TickLog:
    """The engine's ticks, each with the inputs of the tick program it
    dispatched (kept on the device until read): the live rows' work is read
    from what the engine sent, so the harness holds no copy of its
    chunking policy."""

    def __init__(self, eng):
        self.ticks = []          # (t_start, t_end, inputs or None)
        self._sent = None
        mixed, decode = eng._mixed, eng._decode

        def sent_mixed(params, data, pf_toks, pf_t0, pf_len, dec_toks,
                       dec_pos, dec_active, tables):
            self._sent = ("mixed", pf_toks.shape[1], pf_t0, pf_len,
                          dec_pos, dec_active)
            return mixed(params, data, pf_toks, pf_t0, pf_len, dec_toks,
                         dec_pos, dec_active, tables)

        def sent_decode(params, data, toks, pos, tables):
            live = sorted(r.slot for r in eng.scheduler.active)
            self._sent = ("decode", pos, live)
            return decode(params, data, toks, pos, tables)

        eng._mixed, eng._decode = sent_mixed, sent_decode

    def step(self, eng):
        self._sent = None
        t0 = time.time()
        eng.step()
        self.ticks.append((t0, time.time(), self._sent))


def tick_work(tick) -> tuple:
    """(t_start, t_end, prefill spans, decode positions) of one tick: a
    span (a, b, last) for each slot the tick program prefilled, rows [a, b)
    of its prompt, ``last`` if they reach its end; the position of each
    live slot it decoded."""
    t0, t1, sent = tick
    spans, decode = [], []
    if sent is not None and sent[0] == "mixed":
        _, chunk, pf_t0, pf_len, pos, active = sent
        for a, n in zip(np.asarray(pf_t0), np.asarray(pf_len)):
            if n > 0:                         # a row of 0 length is inert
                b = min(int(a) + chunk, int(n))
                spans.append((int(a), b, b == int(n)))
        decode = [int(p) for p, on in zip(np.asarray(pos),
                                          np.asarray(active)) if on]
    elif sent is not None:
        _, pos, live = sent
        pos = np.asarray(pos)
        decode = [int(pos[s]) for s in live]
    return t0, t1, spans, decode


def _run_until(eng, tlog, done, limit_s):
    t_end = time.time() + limit_s
    while not done() and time.time() < t_end and not eng.scheduler.idle():
        tlog.step(eng)


class Server:
    """The engine on the seed's weights, with every token stamped."""

    def __init__(self, cell, seed: int):
        from repro.serving import Engine

        mix, conf = cell.traffic, cell.config
        self.a = arch_of(conf)
        self.params = weights.make(self.a, seed)
        self.eng = Engine(program_config(conf), n_slots=mix["n_slots"],
                          max_len=mix["max_len"], params=self.params,
                          prefix_cache=bool(mix["prefix_cache"]))
        self.tlog = TickLog(self.eng)
        self.stamps: dict[int, list] = {}
        self.eng.on_token = lambda req, tok: self.stamps.setdefault(
            req.rid, []).append(time.time())

    def warm_up(self, mix: dict, seed: int) -> int:
        """Drive every program and host path the window uses."""
        for r in traffic.warmup_requests(mix, mix["n_slots"], seed,
                                         self.a["vocab"]):
            self.eng.submit(r.prompt, max_new=r.max_new)
        first = len(self.tlog.ticks)
        _run_until(self.eng, self.tlog, lambda: False, 600)
        return len(self.tlog.ticks) - first

    def window(self, mix: dict, seconds: float, seed: int, ctx) -> dict:
        """One open-loop window after the mix's pre-roll; then the engine
        runs on (no new requests) until every request due in the window
        has finished, at most ``drain_s``."""
        eng = self.eng
        sched = traffic.serve_schedule(mix, seconds, seed, self.a["vocab"])
        tlog = self.tlog
        first = len(tlog.ticks)
        reqs = []
        t0 = time.time() + float(mix.get("preroll_s", 0))
        t1 = t0 + seconds
        served = [latency.Served(due=t0 + r.due, tokens=[]) for r in sched]
        trace_from = t0 + max(0.0, (seconds - mix["trace_seconds"]) / 2)
        traced = None                   # [first, end) tick indices traced
        depth = []                      # (time, requests waiting)
        i = 0
        opened = False                  # the window has opened
        while True:
            now = time.time()
            while i < len(sched) and served[i].due <= now:
                req = eng.submit(sched[i].prompt, max_new=sched[i].max_new)
                served[i].sent = now
                self.stamps[req.rid] = served[i].tokens
                reqs.append(req)
                i += 1
            if ctx.enabled and traced is None and now >= trace_from:
                ctx.start()
                traced = [len(tlog.ticks), None]
            elif (traced is not None and traced[1] is None
                  and now >= trace_from + mix["trace_seconds"]):
                ctx.stop()
                traced[1] = len(tlog.ticks)
            if now >= t1:
                break
            if now >= t0:
                if not opened:
                    ctx.open()
                    opened = True
                depth.append((now - t0, eng.scheduler.pending))
            if eng.scheduler.idle():
                nxt = served[i].due if i < len(sched) else t1
                time.sleep(max(0.0, min(nxt, t1 if opened else t0) - now))
                continue
            tlog.step(eng)
        if traced is not None and traced[1] is None:
            ctx.stop()
            traced[1] = len(tlog.ticks)
        if not opened:
            ctx.open()
        compiles = ctx.close()
        due = [r for r, s in zip(reqs, served) if s.due >= t0]
        _run_until(eng, tlog, lambda: all(r.done for r in due),
                   mix["drain_s"])
        return {"stats": latency.summarize(served, t0, t1), "reqs": due,
                "t0": t0,
                "served": [s for s in served if s.due >= t0],
                "ticks": tlog.ticks[first:], "depth": depth,
                "traced": traced and [k - first for k in traced],
                "compiles": compiles}

    def drain(self, limit_s: float = 600) -> None:
        _run_until(self.eng, self.tlog, lambda: False, limit_s)


def run(cell, seed: int, seconds: float, ctx, t_process: float,
        log=print) -> dict:
    import jax

    mix = cell.traffic
    t_start = time.time()
    srv = Server(cell, seed)
    t_built = time.time()
    warm = srv.warm_up(mix, seed)
    t_warm = time.time()
    w = srv.window(mix, seconds, seed, ctx)
    setup_s = w["t0"] - t_process           # the pre-roll is set-up
    log(f"[serve] set-up: process and JAX start {t_start - t_process:.3f} s, "
        f"weights and engine {t_built - t_start:.3f} s, warm-up "
        f"{t_warm - t_built:.3f} s ({warm} ticks), pre-roll "
        f"{w['t0'] - t_warm:.3f} s")
    stats, reqs = w["stats"], w["reqs"]
    log(f"[serve] window {seconds} s: {stats['attempted']} requests due, "
        f"{len(reqs)} sent, {sum(1 for r in reqs if r.done)} finished, "
        f"{len(w['ticks'])} ticks; generator late p50 "
        f"{stats['send_late_p50_ms']} ms, max {stats['send_late_max_ms']} ms")
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    finished = [r for r in reqs if r.done and r.out]
    timelines = [(s.due, r.timeline()) for s, r in zip(w["served"], reqs)]
    traced = w["traced"]
    params, a = srv.params, srv.a
    srv.eng.cache.data = None
    del srv
    gc.collect()
    checks = check(params, a, finished, cell.limits, seed, log)
    return {
        "setup_s": setup_s, "stats": stats, "memory_peak_bytes": peak,
        "checks": checks, "timelines": timelines, "arch": a,
        "traced_ticks": ([tick_work(t) for t in w["ticks"][traced[0]:
                                                            traced[1]]]
                         if traced else []),
        "compiles_in_window": w["compiles"], "warmup_ticks": warm,
    }


def sample(finished, k: int, seed: int):
    """The longest finished request and k - 1 others drawn from the seed."""
    if not finished:
        return []
    order = sorted(finished, key=lambda r: (-(r.prompt_len + len(r.out)),
                                            r.rid))
    rest = order[1:]
    rng = np.random.default_rng([seed % 2**64, 5])
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [order[0]] + [rest[j] for j in sorted(pick)]


def served_gaps(params, a, reqs, num, seq_len: int, rows: int):
    """For each request: (reference logits at the served positions, served
    tokens).  The sequence is the prompt and the served tokens, padded at
    the end to ``seq_len`` (padding never reaches an earlier position)."""
    import jax.numpy as jnp

    items = tuple(sorted(a.items()))
    out = []
    for r in reqs:
        toks = np.concatenate([r.prompt, np.asarray(r.out[:-1], np.int32)])
        n_out = len(r.out)
        seq = np.zeros((seq_len,), np.int32)
        seq[:len(toks)] = toks
        pos = np.full((rows,), len(r.prompt) - 1, np.int32)
        pos[:n_out] = len(r.prompt) - 1 + np.arange(n_out)
        z = reference.logits_at(params, jnp.asarray(seq), jnp.asarray(pos),
                                items, num)
        out.append((np.asarray(z)[:n_out], np.asarray(r.out, np.int64)))
    return out


def widest_gap(pairs) -> float:
    """Largest amount by which a served token's reference logit lies below
    the reference's best logit at its position."""
    gaps = [float(np.max(z - z[np.arange(len(t)), t][:, None]))
            for z, t in pairs]
    return max(gaps)


def check(params, a, finished, limits, seed, log) -> list:
    reqs = sample(finished, int(limits["sample_requests"]), seed)
    n_tok = sum(len(r.out) for r in reqs)
    if not reqs:
        return [{"name": "served_logit_gap", "value": None,
                 "limit": limits["served_logit_gap"], "ok": False}]
    t = time.time()
    pairs = served_gaps(params, a, reqs, reference.Num(False),
                        int(limits["ref_seq_len"]), int(limits["ref_rows"]))
    gap = widest_gap(pairs)
    log(f"[check] reference over {len(reqs)} requests, {n_tok} served "
        f"tokens, in {time.time() - t:.1f} s")
    return [{"name": "served_logit_gap", "value": gap,
             "limit": limits["served_logit_gap"],
             "ok": bool(gap <= limits["served_logit_gap"])},
            {"name": "served_tokens_checked", "value": n_tok,
             "limit": limits["min_tokens_checked"],
             "ok": n_tok >= limits["min_tokens_checked"]}]
