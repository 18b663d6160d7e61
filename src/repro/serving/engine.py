"""Continuous-batching serving engine on the paged NSA KV-cache.

Replaces the old fixed-batch loop in ``launch/serve.py``: prompts of any
length are admitted as slots and pages free up, and every engine tick is ONE
fused dispatch (``transformer.lm_paged_mixed_step``) that advances each
prefilling slot by one bounded chunk AND decodes one token for every active
slot at its own absolute position (a (B,) position vector, not a shared
scalar).  Decode therefore never stalls behind a long co-admitted prompt's
chunk loop — vLLM-style continuous batching — and the per-tick prefill work
is bounded by the scheduler's token budget.  The decode sub-step runs the
Pallas paged-decode kernel (``kernels/paged_decode.py``) by default, which
folds the slot batch into the MXU M dimension and reads KV through the page
table at page granularity.

The NSA decode tick reads only the pages its branches touch — compressed
rows, the top-T selected pages and the sliding window — so a tick is
O(N/stride + T·B_K + W) per slot regardless of context depth.

``fused=False`` keeps the previous sequential engine (prefill the whole
admission batch to completion, then decode) — the A/B reference for the
fused tick's token-identical-outputs guarantee.

Latency accounting: ``first_token_t`` is stamped PER REQUEST, after that
request's first token has been materialized on host (the blocking argmax
sync is inside the stamp, and inside ``prefill_s``) — never one shared
pre-sync timestamp for a whole admission batch.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

import jax
import jax.numpy as jnp

from repro import telemetry
from repro.models import build, transformer
from repro.serving.cache import PagedNSACache
from repro.serving.prefix import PrefixCache
from repro.serving.scheduler import Request, Scheduler

SUPPORTED_FAMILIES = ("lm", "moe")


class Engine:
    """Paged continuous-batching engine for decoder-only attention models.

    ``mesh=`` (a ``("data", "model")`` jax Mesh) routes construction to
    ``repro.serving.sharded.ShardedEngine`` when the mesh spans more than one
    device: KV-head-sharded page pools, slot-sharded engine replicas, one
    ``shard_map``ped dispatch per tick.  A 1x1 mesh is byte-identical to the
    plain single-device engine (this class).
    """

    def __new__(cls, *args, mesh=None, **kwargs):
        if cls is Engine and mesh is not None and mesh.devices.size > 1:
            from repro.serving.sharded import ShardedEngine
            return super().__new__(ShardedEngine)
        return super().__new__(cls)

    def __init__(self, cfg, n_slots: int = 4, max_len: int = 1024, *,
                 num_pages: int | None = None, prefill_chunk: int | None = None,
                 params=None, seed: int = 0, backend: str | None = None,
                 mesh=None,
                 admit_limit: int | None = None,
                 prefill_token_budget: int | None = None,
                 fused: bool = True,
                 retain_outputs: int | None = 1024,
                 prefix_cache: bool = False,
                 metrics: "telemetry.Registry | None" = None,
                 metrics_port: int | None = None):
        if cfg.family not in SUPPORTED_FAMILIES:
            raise NotImplementedError(
                f"paged serving supports families {SUPPORTED_FAMILIES}, got "
                f"'{cfg.family}' (ssm/hybrid/encdec state is not paged KV)")
        del mesh   # 1-device meshes are byte-identical to the plain engine
        if backend is not None:      # override cfg.nsa.policy.paged_backend
            cfg = dataclasses.replace(cfg, nsa=dataclasses.replace(
                cfg.nsa, policy=dataclasses.replace(
                    cfg.nsa.policy, paged_backend=backend)))
        self.cfg = cfg
        self.model = build(cfg)
        self.params = self._init_params(params, seed)
        self.cache = self._make_cache(cfg, n_slots, max_len,
                                      num_pages=num_pages)
        p = self.cache.page_size
        # chunk-rounded prompts must fit one slot's page budget, so the
        # chunk never exceeds the slot's addressable rows
        self.prefill_chunk = min(prefill_chunk or 4 * p,
                                 self.cache.max_pages * p)
        # radix prefix cache (opt-in): admission matches prompts against it,
        # matched blocks alias shared physical pages and skip prefill; the
        # trie holds its own page references, so with it enabled pool.used
        # stays > 0 after a drain until eviction/reset
        self._prefix = self._make_prefix() if prefix_cache else None
        self.cache.prefix = self._prefix
        self.scheduler = Scheduler(self.cache, self.prefill_chunk,
                                   retain_outputs=retain_outputs,
                                   prefix=self._prefix)
        self.scheduler.on_release = self._on_release
        self.n_slots = n_slots
        # caps one step's admission batch (everything admitted together is
        # prefilled together in sequential mode, so this bounds how many
        # short prompts a long co-admitted one can stall); None = fill all
        # free slots
        self.admit_limit = admit_limit
        # fused mode: cap on prefill chunk tokens processed per tick
        # (scheduler admission enforces it; None = no cap beyond slot count)
        self.prefill_token_budget = prefill_token_budget
        self.fused = fused
        # per-request streaming hooks: on_token(req, tok) fires after the
        # token is on host (and appended to req.out); on_finish(req) after
        # the slot is recycled.  Set by AsyncEngine or any caller.
        self.on_token = None
        self.on_finish = None
        self._pf_pos: dict[int, int] = {}    # slot -> next chunk offset

        self._build_dispatch(cfg)
        self._last_tokens = np.zeros((n_slots,), np.int32)
        # the engine's own always-on registry: ``summary()``/``stats`` are
        # views over its snapshot, so core accounting never depends on
        # whether *global* telemetry (JSONL sink, dispatch counters,
        # profiler annotations) is switched on.  Pass ``metrics=`` to share
        # a registry across engines.
        self.telemetry = (metrics if metrics is not None
                          else telemetry.Registry(enabled=True, name="engine"))
        self._tick_no = 0
        # optional Prometheus pull endpoint over THIS engine's registry
        # (port 0 picks a free one; see handle.port / handle.url)
        self.metrics_server = (
            telemetry.serve_metrics(metrics_port, registry=self.telemetry)
            if metrics_port is not None else None)

    # --------------------------------------------------- construction hooks
    # Overridden by ``serving.sharded.ShardedEngine``: sharded params and
    # cache facade, per-replica prefix router, shard_mapped dispatch.  The
    # scheduler, tick loop, and accounting above them are shared verbatim.
    def _init_params(self, params, seed):
        """``params`` as given, else made from ``seed`` in one jitted program
        (run eagerly, init would also hold every stacked weight's float32
        draw on the device)."""
        if params is not None:
            return params
        return jax.jit(self.model.init)(jax.random.PRNGKey(seed))

    def _make_cache(self, cfg, n_slots, max_len, *, num_pages):
        return PagedNSACache(cfg, n_slots, max_len, num_pages=num_pages)

    def _make_prefix(self):
        return PrefixCache(self.cache)

    def _build_dispatch(self, cfg) -> None:
        # cfg is closed over (static); cache buffers are donated per call
        self._decode = jax.jit(
            lambda params, data, toks, pos, tables:
                transformer.lm_paged_decode_step(params, data, toks, pos,
                                                 tables, cfg),
            donate_argnums=(1,))
        self._prefill = jax.jit(
            lambda params, data, toks, t0, length, tables:
                transformer.lm_paged_prefill_chunks(params, data, toks, t0,
                                                    length, tables, cfg),
            donate_argnums=(1,))
        self._mixed = jax.jit(
            lambda params, data, pf_toks, pf_t0, pf_len, dec_toks, dec_pos,
            dec_active, tables:
                transformer.lm_paged_mixed_step(
                    params, data, pf_toks, pf_t0, pf_len, dec_toks, dec_pos,
                    dec_active, tables, cfg),
            donate_argnums=(1,))

    # ------------------------------------------------ telemetry shortcuts
    def _count(self, name: str, n: float = 1, **labels) -> None:
        self.telemetry.counter(name, **labels).inc(n)

    def _tick_accounting(self, kind: str, seconds: float) -> None:
        self._count("engine_ticks_total", kind=kind)
        self._count("engine_tick_seconds_total", seconds, kind=kind)

    @property
    def stats(self) -> dict:
        """Legacy stats-dict view, derived from the telemetry snapshot
        (same keys as the pre-telemetry ad-hoc dict)."""
        snap = self.telemetry.snapshot()
        cv, gs = telemetry.counter_value, telemetry.gauge_stats
        return {
            "decoded_tokens": int(cv(snap, "engine_decoded_tokens_total")),
            "decode_ticks": int(cv(snap, "engine_ticks_total", kind="decode")),
            "decode_s": cv(snap, "engine_tick_seconds_total", kind="decode"),
            "prefill_tokens": int(cv(snap, "engine_prefill_tokens_total")),
            "prefill_s": cv(snap, "engine_tick_seconds_total",
                            kind="prefill"),
            "mixed_ticks": int(cv(snap, "engine_ticks_total", kind="mixed")),
            "mixed_s": cv(snap, "engine_tick_seconds_total", kind="mixed"),
            "peak_page_util": gs(snap, "engine_page_util", pool="raw")["max"],
            "peak_cmp_page_util": gs(snap, "engine_page_util",
                                     pool="cmp")["max"],
        }

    # ------------------------------------------------------------- intake
    def submit(self, prompt, max_new: int = 16, eos_id: int | None = None
               ) -> Request:
        return self.scheduler.submit(
            Request(prompt=np.asarray(prompt), max_new=max_new, eos_id=eos_id))

    def _on_release(self, req: Request) -> None:
        """Slot recycled: drop stale per-slot decode state so the freed
        slot's ride-along decode rows are reproducible (token 0 on the dump
        page) and a later occupant never inherits the old last token."""
        self._last_tokens[req.slot] = 0
        self._pf_pos.pop(req.slot, None)
        self._count("engine_finished_requests_total")
        self.telemetry.event("request", rid=req.rid, prompt_len=req.prompt_len,
                             new_tokens=req.num_out, **req.timeline())
        if self.on_finish is not None:
            self.on_finish(req)

    def _emit(self, req: Request, tok: int) -> None:
        req.out.append(tok)
        self._last_tokens[req.slot] = tok
        if self.on_token is not None:
            self.on_token(req, tok)

    def _track_util(self) -> dict:
        """Per-tick samples: queue depth, slot occupancy, raw+compressed
        page-pool utilization (gauges track last/min/max, so the summary's
        peaks fall out of the snapshot)."""
        util = self.cache.utilization()
        self.telemetry.gauge("engine_page_util", pool="raw").set(util["raw"])
        self.telemetry.gauge("engine_page_util", pool="cmp").set(util["cmp"])
        self.telemetry.gauge("engine_queue_depth").set(self.scheduler.pending)
        self.telemetry.gauge("engine_active_slots").set(
            len(self.scheduler.active))
        if self._prefix is not None:
            self.telemetry.gauge("prefix_blocks_cached").set(
                self._prefix.blocks_cached)
        return util

    # ------------------------------------------------------- prefix cache
    def _count_prefix_hits(self, admitted: list[Request]) -> None:
        for r in admitted:
            if r.cached_tokens:
                self._count("prefix_cache_hit_total")
                self._count("prefix_cache_blocks_reused_total",
                            r.cached_tokens // self.cache.page_size)

    def _register_prefix(self, req: Request) -> None:
        """Index the request's fully-materialized prompt blocks (called once
        its prefill completed — later requests sharing the prefix alias
        these physical pages and skip the work)."""
        if self._prefix is not None:
            self._prefix.insert(req.prompt, req.slot)

    # ------------------------------------------------------------ prefill
    def _prefill_requests(self, reqs: list[Request]) -> None:
        """Sequential-mode prefill: stream ALL newly admitted prompts
        together through the fixed-shape batched chunk jit, one dispatch per
        chunk step for the whole admission batch (padded to ``n_slots`` rows
        so the jit never recompiles).  Slots whose (shorter) prompt is
        already fully written ride along inertly — their writes land on the
        dump page."""
        if not reqs:
            return
        t_start = time.time()
        c = self.prefill_chunk
        bsz = self.n_slots
        lens = [len(r.prompt) for r in reqs]
        # prefix-cached tokens are already materialized in shared pages:
        # each slot's chunk stream starts at its own absolute offset
        skip = [r.cached_tokens for r in reqs]
        rem = [n - s for n, s in zip(lens, skip)]      # >= 1 (match cap)
        chunks = [-(-n // c) for n in rem]
        max_chunks = max(chunks)
        toks = np.zeros((bsz, max_chunks * c), np.int32)
        length = np.zeros((bsz,), np.int32)
        base = np.zeros((bsz,), np.int32)
        for i, r in enumerate(reqs):
            toks[i, :rem[i]] = r.prompt[skip[i]:]
            length[i] = lens[i]
            base[i] = skip[i]
        tables = self.cache.views([r.slot for r in reqs], batch_size=bsz)
        length_j = jnp.asarray(length)
        last_logits = [None] * len(reqs)
        for kc in range(max_chunks):
            start = kc * c
            with telemetry.span("engine.prefill_chunk",
                                registry=self.telemetry):
                logits, self.cache.data = self._prefill(
                    self.params, self.cache.data,
                    jnp.asarray(toks[:, start:start + c]),
                    jnp.asarray(base + start), length_j, tables)
            if kc == 0:                      # whole batch got its 1st chunk
                t_chunk = time.time()
                for r in reqs:
                    if r.first_chunk_t is None:
                        r.first_chunk_t = t_chunk
            for i in range(len(reqs)):
                if kc == chunks[i] - 1:          # chunk with the last token
                    last_logits[i] = logits[i, (lens[i] - 1) - skip[i] - start,
                                            :self.cfg.vocab]
        with telemetry.span("engine.host_sync", registry=self.telemetry):
            for i, r in enumerate(reqs):
                self.cache.lengths[r.slot] = lens[i]
                self._register_prefix(r)
                tok = int(jnp.argmax(last_logits[i]))   # blocking host sync
                self._emit(r, tok)
                r.first_token_t = time.time()    # per request, post-sync
                self._count("engine_prefill_tokens_total", rem[i])
        self._tick_accounting("prefill", time.time() - t_start)

    def _prefill_request(self, req: Request) -> None:
        """Single-request prefill (compat wrapper over the batched path)."""
        self._prefill_requests([req])

    # -------------------------------------------------------------- ticks
    def _finish_ready(self) -> list[Request]:
        done = []
        for req in self.scheduler.active:
            if (len(req.out) >= req.max_new
                    or (req.eos_id is not None and req.out
                        and req.out[-1] == req.eos_id)):
                self.scheduler.release(req)
                done.append(req)
        return done

    def _decode_tick(self) -> None:
        """One token for every active slot at its own position."""
        t0 = time.time()
        pos = jnp.asarray(self.cache.lengths, jnp.int32)
        with telemetry.span("engine.decode", registry=self.telemetry):
            logits, self.cache.data = self._decode(
                self.params, self.cache.data, jnp.asarray(self._last_tokens),
                pos, self.cache.views())
        with telemetry.span("engine.host_sync", registry=self.telemetry):
            nxt = np.asarray(jnp.argmax(logits[:, :self.cfg.vocab], axis=-1),
                             np.int32)
        for req in self.scheduler.active:
            s = req.slot
            self._emit(req, int(nxt[s]))
            self.cache.lengths[s] += 1
            self._count("engine_decoded_tokens_total")
        self._tick_accounting("decode", time.time() - t0)

    # --------------------------------------------------------- fused tick
    def _prefill_tokens_in_flight(self) -> int:
        """Chunk tokens the CURRENT prefilling slots will consume next tick
        (the scheduler's admission budget adds to this)."""
        total = 0
        for req in self.scheduler.active:
            t0 = self._pf_pos.get(req.slot)
            if t0 is not None:
                total += min(self.prefill_chunk, len(req.prompt) - t0)
        return total

    def _step_fused(self) -> dict:
        """ONE fused dispatch: a bounded prefill chunk for admitting slots +
        one decode token for active slots, co-scheduled."""
        with telemetry.span("engine.admit", registry=self.telemetry) as sp:
            admitted = self.scheduler.admit(
                self.admit_limit, token_budget=self.prefill_token_budget,
                tokens_in_flight=self._prefill_tokens_in_flight())
            sp.annotate(admitted=len(admitted))
        self._count("engine_admitted_requests_total", len(admitted))
        self._count_prefix_hits(admitted)
        for r in admitted:
            # prefill resumes past the prefix-cached tokens (0 on a miss)
            self._pf_pos[r.slot] = r.cached_tokens
        util = self._track_util()

        c, bsz = self.prefill_chunk, self.n_slots
        prefilling = [r for r in self.scheduler.active
                      if r.slot in self._pf_pos]
        decoding = [r for r in self.scheduler.active
                    if r.slot not in self._pf_pos]
        if not prefilling and not decoding:
            return {"admitted": admitted, "finished": [], "active": 0,
                    "pending": self.scheduler.pending, "page_util": util,
                    "prefill_chunk_tokens": 0}

        t_tick = time.time()
        chunk_tokens = 0
        if prefilling:
            pf_toks = np.zeros((bsz, c), np.int32)
            pf_t0 = np.zeros((bsz,), np.int32)
            pf_len = np.zeros((bsz,), np.int32)   # 0 rows are inert
            for r in prefilling:
                s, t0 = r.slot, self._pf_pos[r.slot]
                n = min(c, len(r.prompt) - t0)
                pf_toks[s, :n] = r.prompt[t0:t0 + n]
                pf_t0[s], pf_len[s] = t0, len(r.prompt)
                chunk_tokens += n
            dec_active = np.zeros((bsz,), bool)
            for r in decoding:
                dec_active[r.slot] = True
            # the fused dispatch IS the tick's prefill-chunk phase (decode
            # rides along in the same launch)
            with telemetry.span("engine.prefill_chunk",
                                registry=self.telemetry,
                                fused=bool(decoding),
                                args={"rows": bsz * c,
                                      "live_rows": chunk_tokens,
                                      "decode_rows": len(decoding)}):
                pf_logits, dec_logits, self.cache.data = self._mixed(
                    self.params, self.cache.data, jnp.asarray(pf_toks),
                    jnp.asarray(pf_t0), jnp.asarray(pf_len),
                    jnp.asarray(self._last_tokens),
                    jnp.asarray(self.cache.lengths, jnp.int32),
                    jnp.asarray(dec_active), self.cache.views())
            t_chunk = time.time()
            for r in prefilling:             # chunk dispatched for these
                if r.first_chunk_t is None:
                    r.first_chunk_t = t_chunk
        else:   # steady-state decode: skip the (B, C) prefill sub-step
            with telemetry.span("engine.decode", registry=self.telemetry,
                                args={"rows": bsz,
                                      "live_rows": len(decoding)}):
                dec_logits, self.cache.data = self._decode(
                    self.params, self.cache.data,
                    jnp.asarray(self._last_tokens),
                    jnp.asarray(self.cache.lengths, jnp.int32),
                    self.cache.views())
            pf_logits = None

        with telemetry.span("engine.host_sync", registry=self.telemetry):
            # prefill progress: advance each slot one chunk; a slot whose
            # chunk covered its last prompt token materializes its FIRST
            # token now
            for r in prefilling:
                s, t0 = r.slot, self._pf_pos[r.slot]
                self._count("engine_prefill_tokens_total",
                            min(c, len(r.prompt) - t0))
                if t0 + c >= len(r.prompt):
                    tok = int(jnp.argmax(            # blocking host sync
                        pf_logits[s, (len(r.prompt) - 1) - t0,
                                  :self.cfg.vocab]))
                    del self._pf_pos[s]
                    self.cache.lengths[s] = len(r.prompt)
                    self._register_prefix(r)
                    self._emit(r, tok)
                    r.first_token_t = time.time()    # per request, post-sync
                else:
                    self._pf_pos[s] = t0 + c
            if decoding:
                nxt = np.asarray(jnp.argmax(dec_logits[:, :self.cfg.vocab],
                                            axis=-1), np.int32)
                for r in decoding:
                    s = r.slot
                    self._emit(r, int(nxt[s]))
                    self.cache.lengths[s] += 1
                    self._count("engine_decoded_tokens_total")

        dt = time.time() - t_tick
        kind = ("mixed" if prefilling and decoding
                else "decode" if decoding else "prefill")
        self._tick_accounting(kind, dt)
        finished = self._finish_ready()
        return {"admitted": admitted, "finished": finished,
                "active": len(self.scheduler.active),
                "pending": self.scheduler.pending, "page_util": util,
                "prefill_chunk_tokens": chunk_tokens}

    def _step_sequential(self) -> dict:
        """Legacy two-phase iteration: admit + full prefill, then decode."""
        with telemetry.span("engine.admit", registry=self.telemetry) as sp:
            admitted = self.scheduler.admit(self.admit_limit)
            sp.annotate(admitted=len(admitted))
        self._count("engine_admitted_requests_total", len(admitted))
        self._count_prefix_hits(admitted)
        self._prefill_requests(admitted)
        util = self._track_util()
        finished = self._finish_ready()       # requests done at prefill
        if self.scheduler.active:
            self._decode_tick()
            finished += self._finish_ready()
        return {"admitted": admitted, "finished": finished,
                "active": len(self.scheduler.active),
                "pending": self.scheduler.pending, "page_util": util}

    def step(self) -> dict:
        """One engine iteration (fused mixed tick unless ``fused=False``)."""
        self._tick_no += 1
        with telemetry.span("engine.tick", registry=self.telemetry) as sp:
            out = (self._step_fused() if self.fused
                   else self._step_sequential())
            sp.annotate(tick=self._tick_no)
        self.telemetry.event(
            "tick", tick=self._tick_no,
            queue_depth=self.scheduler.pending,
            active_slots=out["active"],
            admitted=len(out["admitted"]), finished=len(out["finished"]),
            page_util_raw=out["page_util"]["raw"],
            page_util_cmp=out["page_util"]["cmp"],
            prefill_chunk_tokens=out.get("prefill_chunk_tokens", 0))
        return out

    def run(self, requests=None, *, max_steps: int | None = None) -> dict:
        """Drive until all traffic (queued + active) has drained."""
        if requests:
            for r in requests:
                self.scheduler.submit(r)
        steps = 0
        while not self.scheduler.idle():
            self.step()
            steps += 1
            if max_steps is not None and steps >= max_steps:
                break
        return self.summary()

    def summary(self) -> dict:
        """Serving summary, derived from the telemetry snapshot (the keys
        predate the telemetry subsystem and are kept byte-compatible —
        ``serve_bench``/``check_regression`` gate on them)."""
        snap = self.telemetry.snapshot()
        cv, gs = telemetry.counter_value, telemetry.gauge_stats
        tick_s = lambda kind: cv(snap, "engine_tick_seconds_total", kind=kind)
        ticks = lambda kind: cv(snap, "engine_ticks_total", kind=kind)
        decoded = int(cv(snap, "engine_decoded_tokens_total"))
        prefill_tokens = int(cv(snap, "engine_prefill_tokens_total"))
        # overlapped accounting: during a mixed tick BOTH streams progress,
        # so each stream's throughput window includes mixed time
        decode_window = tick_s("decode") + tick_s("mixed")
        prefill_window = tick_s("prefill") + tick_s("mixed")
        decode_ticks = ticks("decode") + ticks("mixed")
        admitted = cv(snap, "engine_admitted_requests_total")
        return {
            "requests_finished": len(self.scheduler.finished),
            "decoded_tokens": decoded,
            "decode_tokens_per_s": decoded / max(decode_window, 1e-9),
            "prefill_tokens_per_s":
                prefill_tokens / max(prefill_window, 1e-9),
            "decode_ms_per_tick": 1e3 * decode_window / max(decode_ticks, 1),
            "mixed_ticks": int(ticks("mixed")),
            "peak_page_util": gs(snap, "engine_page_util", pool="raw")["max"],
            "peak_cmp_page_util": gs(snap, "engine_page_util",
                                     pool="cmp")["max"],
            # prefix cache (0 / absent-series defaults when disabled)
            "prefix_hit_rate":
                cv(snap, "prefix_cache_hit_total") / max(admitted, 1),
            "prefix_blocks_reused":
                int(cv(snap, "prefix_cache_blocks_reused_total")),
            "prefix_blocks_cached":
                int(gs(snap, "prefix_blocks_cached")["last"]),
            # bounded retention: requests evicted past ``retain_outputs``
            # keep counts + timeline but no token lists (see Scheduler)
            "outputs": {r.rid: list(r.out) for r in self.scheduler.finished
                        if not r.out_evicted},
        }

    def timelines(self) -> dict:
        """{rid: per-request timeline} for every finished request (retained
        through output eviction — stamps are five floats)."""
        return {r.rid: r.timeline() for r in self.scheduler.finished}
