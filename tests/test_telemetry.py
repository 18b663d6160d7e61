"""repro.telemetry: metrics core (counters/gauges/histograms, exposition,
disabled-mode no-ops), spans (nesting, timing monotonicity, JSONL events),
attention-dispatch accounting, per-request serving timelines and bounded
retention."""
import json
import time

import pytest

import jax

from repro import telemetry
from repro.attention import (AttentionRequest, BackendResolutionError,
                             NSAConfig, explain, near_misses, nsa_attention,
                             resolve)
from repro.configs import get_config, reduced
from repro.serving import Engine, Request
from repro.serving.async_engine import AsyncEngine

jax.config.update("jax_platform_name", "cpu")


@pytest.fixture(autouse=True)
def _global_telemetry_reset():
    """Global telemetry is process state: leave every test with it off and
    empty, the way the process starts."""
    yield
    telemetry.disable()
    telemetry.registry().clear()


# ------------------------------------------------------------ metrics core
def test_counter_gauge_histogram_basics():
    reg = telemetry.Registry(enabled=True, name="t")
    reg.counter("req_total", backend="fsa").inc()
    reg.counter("req_total", backend="fsa").inc(2)    # get-or-create: same series
    reg.counter("req_total", backend="ref").inc()
    reg.gauge("depth").set(3)
    reg.gauge("depth").set(1)
    reg.histogram("lat_ms", buckets=(1.0, 5.0)).observe(0.5)
    reg.histogram("lat_ms", buckets=(1.0, 5.0)).observe(7.0)

    snap = reg.snapshot()
    assert telemetry.counter_value(snap, "req_total", backend="fsa") == 3
    assert telemetry.counter_value(snap, "req_total", backend="ref") == 1
    assert telemetry.counter_value(snap, "req_total", backend="nope") == 0
    g = telemetry.gauge_stats(snap, "depth")
    assert (g["last"], g["min"], g["max"], g["samples"]) == (1, 1, 3, 2)
    h = snap["histograms"]["lat_ms"][""]
    assert h["count"] == 2 and h["sum"] == 7.5
    assert h["buckets"] == {"1.0": 1, "5.0": 1, "+Inf": 2}   # cumulative


def test_disabled_registry_is_noop():
    reg = telemetry.Registry(enabled=False)
    c = reg.counter("x")
    assert c is telemetry.NOOP
    c.inc()
    reg.gauge("y").set(5)
    reg.histogram("z").observe(1.0)
    assert reg.snapshot() == {"counters": {}, "gauges": {}, "histograms": {}}
    assert reg.exposition() == ""


def test_global_registry_disabled_by_default():
    assert not telemetry.enabled()
    assert telemetry.registry().counter("anything") is telemetry.NOOP
    telemetry.enable()
    assert telemetry.enabled()
    assert telemetry.registry().counter("anything") is not telemetry.NOOP


def test_exposition_golden():
    reg = telemetry.Registry(enabled=True, name="t")
    reg.counter("req_total", backend="fsa").inc(3)
    reg.gauge("depth").set(2)
    h = reg.histogram("lat_ms", buckets=(1.0, 5.0), op="x")
    for v in (0.5, 3.0, 7.0):
        h.observe(v)
    assert reg.exposition() == (
        '# TYPE req_total counter\n'
        'req_total{backend="fsa"} 3\n'
        '# TYPE depth gauge\n'
        'depth 2\n'
        '# TYPE lat_ms histogram\n'
        'lat_ms_bucket{op="x",le="1.0"} 1\n'
        'lat_ms_bucket{op="x",le="5.0"} 2\n'
        'lat_ms_bucket{op="x",le="+Inf"} 3\n'
        'lat_ms_sum{op="x"} 10.5\n'
        'lat_ms_count{op="x"} 3\n')


# ------------------------------------------------------------------- spans
def test_span_nesting_and_timing_monotonicity():
    reg = telemetry.Registry(enabled=True, name="t")
    with telemetry.span("outer", registry=reg):
        time.sleep(0.002)
        with telemetry.span("inner", registry=reg):
            time.sleep(0.002)
    snap = reg.snapshot()
    spans = snap["histograms"]["span_ms"]
    outer = spans['span="outer"']
    inner = spans['span="inner"']
    assert outer["count"] == 1 and inner["count"] == 1
    # the outer span strictly contains the inner one
    assert outer["sum"] > inner["sum"] > 0


def test_span_noop_when_nothing_enabled():
    # global off, no explicit registry, no sink: the span must not record
    with telemetry.span("dead") as sp:
        sp.annotate(n=1)
    assert telemetry.registry().snapshot()["histograms"] == {}


def test_span_events_carry_depth_and_parent(tmp_path):
    path = str(tmp_path / "events.jsonl")
    telemetry.enable(jsonl=path)
    with telemetry.span("outer"):
        with telemetry.span("inner", stage="x") as sp:
            sp.annotate(items=7)
    telemetry.disable()
    events = [json.loads(line) for line in open(path)]
    spans = {e["name"]: e for e in events if e["kind"] == "span"}
    assert spans["inner"]["parent"] == "outer"
    assert spans["inner"]["depth"] == 1 and spans["outer"]["depth"] == 0
    assert spans["inner"]["stage"] == "x" and spans["inner"]["items"] == 7
    assert spans["outer"]["ms"] >= spans["inner"]["ms"]
    # annotate() fields are event-only: the histogram key stays bounded
    lk = 'span="inner",stage="x"'
    assert lk in telemetry.registry().snapshot()["histograms"]["span_ms"]


# ----------------------------------------------------- dispatch accounting
_CFG = NSAConfig(block_size=16, num_selected=4, cmp_block_size=8,
                 cmp_stride=4, window_size=32, q_block_size=32,
                 min_seq_for_sparse=1)


def _full_qkv(n=32, g=1, h_k=2, d=8):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    return (jax.random.normal(ks[0], (n, g * h_k, d)),
            jax.random.normal(ks[1], (n, h_k, d)),
            jax.random.normal(ks[2], (n, h_k, d)))


def test_dispatch_counter_once_per_call():
    telemetry.enable()
    q, k, v = _full_qkv()
    for _ in range(2):      # eager: one python call = one dispatch
        nsa_attention(None, None, q, k, v, cfg=_CFG, mode="prefill",
                      backend="reference", algorithm="full")
    snap = telemetry.registry().snapshot()
    assert telemetry.counter_value(
        snap, "attention_dispatch_total", backend="reference", mode="prefill",
        algorithm="full") == 2


def test_resolve_fallback_counter():
    telemetry.enable()
    cfg = NSAConfig(block_size=16, num_selected=4, cmp_block_size=8,
                    cmp_stride=4, window_size=32, q_block_size=32,
                    min_seq_for_sparse=4096)
    req = AttentionRequest(mode="prefill", algorithm="nsa", seq_len=64, g=2)
    assert resolve(cfg, req, "fsa").name == "reference"   # dense fallback
    snap = telemetry.registry().snapshot()
    assert telemetry.counter_value(
        snap, "attention_resolve_fallback_total", kind="dense_short_seq",
        mode="prefill") == 1


# --------------------------------------------------------- explain / misses
def test_explain_prints_capability_table():
    req = AttentionRequest(mode="prefill", algorithm="nsa", seq_len=256, g=2)
    text = explain(_CFG, req)
    assert "resolve -> " in text
    assert "reference" in text and "fsa" in text
    assert "OK" in text and "score=" in text


def test_near_misses_in_resolution_error(monkeypatch):
    # the dense reference backend covers every request, so an unservable one
    # only exists without it: differentiable paged training — paged backends
    # are inference-only, the rest do not read paged KV.  The error must
    # name the nearest misses instead of a bare failure.
    from repro.attention import registry as areg
    monkeypatch.setattr(areg, "_REGISTRY", {
        n: b for n, b in areg._REGISTRY.items() if n != "reference"})
    req = AttentionRequest(mode="train", algorithm="nsa", paged=True,
                           needs_grad=True, g=2)
    assert near_misses(req)
    with pytest.raises(BackendResolutionError, match="Nearest misses"):
        resolve(None, req, "auto")
    text = explain(None, req)
    assert "FAILS" in text


# ------------------------------------------------- serving timelines/spans
def test_engine_timelines_spans_and_retention():
    cfg = reduced(get_config("codeqwen1.5-7b"))
    eng = Engine(cfg, n_slots=2, max_len=96, prefill_chunk=32,
                 retain_outputs=1)
    t_before = time.time()
    for prompt_len in (40, 8, 12):
        eng.submit(list(range(1, prompt_len + 1)), max_new=2)
    summary = eng.run()

    assert summary["requests_finished"] == 3
    finished = eng.scheduler.finished
    for r in finished:
        tl = r.timeline()
        # submit <= admit <= first_chunk <= first_token <= finish, all stamped
        keys = list(tl)
        assert keys == ["submit", "admit", "first_chunk", "first_token",
                        "finish"]
        stamps = list(tl.values())
        assert stamps == sorted(stamps)
        assert stamps[0] >= t_before
    # bounded retention: only the newest finished request keeps its tokens
    evicted = [r for r in finished if r.out_evicted]
    kept = [r for r in finished if not r.out_evicted]
    assert len(kept) == 1 and len(evicted) == 2
    for r in evicted:
        assert r.out == [] and r.num_out == 2 and r.prompt_len > 0
        assert r.timeline()     # timeline survives eviction
    assert set(summary["outputs"]) == {kept[0].rid}
    assert set(eng.timelines()) == {r.rid for r in finished}

    # every tick phase is a named span in the engine's telemetry snapshot
    snap = eng.telemetry.snapshot()
    span_keys = "".join(snap["histograms"]["span_ms"])
    for phase in ("engine.tick", "engine.admit", "engine.prefill_chunk",
                  "engine.host_sync"):
        assert phase in span_keys, phase
    # legacy stats keys stay derivable from the snapshot; with max_new=2
    # each request yields one prefill-materialized token + one decoded token
    stats = eng.stats
    assert stats["decoded_tokens"] == summary["decoded_tokens"] == 3
    assert stats["prefill_tokens"] == 40 + 8 + 12
    assert summary["peak_page_util"] > 0


def test_async_engine_timeline_retention_bounded():
    cfg = reduced(get_config("codeqwen1.5-7b"))
    aeng = AsyncEngine(Engine(cfg, n_slots=2, max_len=96, prefill_chunk=32),
                       retain_timelines=2)
    # exercise the retention bookkeeping directly (no event loop needed:
    # _on_finish is the engine-thread hook)
    reqs = [Request(prompt=[1, 2, 3]) for _ in range(3)]
    for r in reqs:
        r.admit_t = r.first_token_t = r.finish_t = r.submit_t
        aeng._on_finish(r)
    assert aeng.timeline(reqs[0].rid) is None          # evicted past the cap
    assert set(aeng.timelines()) == {reqs[1].rid, reqs[2].rid}
    tl = aeng.timeline(reqs[2].rid)
    assert tl["submit"] <= tl["first_token"] <= tl["finish"]


# --------------------------------------------------------- pull endpoint
def test_metrics_pull_endpoint_serves_engine_registry():
    """Engine(metrics_port=0) exposes the engine's always-on registry as a
    Prometheus /metrics endpoint on an ephemeral port."""
    import urllib.request

    import numpy as np

    cfg = reduced(get_config("codeqwen1.5-7b"))
    eng = Engine(cfg, n_slots=1, max_len=64, metrics_port=0)
    try:
        assert eng.metrics_server is not None
        eng.submit(np.arange(8, dtype=np.int32), max_new=2)
        while not eng.scheduler.idle():
            eng.step()
        body = urllib.request.urlopen(eng.metrics_server.url,
                                      timeout=10).read().decode()
        assert "engine_decoded_tokens_total" in body
        assert "# TYPE" in body                 # Prometheus text format
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(
                eng.metrics_server.url.replace("/metrics", "/nope"),
                timeout=10)
    finally:
        eng.metrics_server.stop()


def test_metrics_pull_endpoint_global_registry_late_enable():
    """A server bound to the global registry starts serving real series the
    moment telemetry.enable() runs (registry resolved per scrape)."""
    import urllib.request

    srv = telemetry.serve_metrics(0)
    try:
        before = urllib.request.urlopen(srv.url, timeout=10).read().decode()
        assert "pull_probe_total" not in before
        telemetry.enable()
        telemetry.registry().counter("pull_probe_total").inc(3)
        after = urllib.request.urlopen(srv.url, timeout=10).read().decode()
        assert "pull_probe_total 3" in after
    finally:
        srv.stop()


# ------------------------------------------------------- train-loop spans
def test_train_loop_records_train_step_spans(tmp_path):
    """launch.train wraps each optimizer step in a train.step span: with
    global telemetry on, span_ms series (device-synced) must appear."""
    from repro.launch.mesh import make_mesh
    from repro.launch.train import train_loop
    from repro.runtime.fault_tolerance import FTConfig

    telemetry.enable()
    cfg = reduced(get_config("mamba2-130m"))
    mesh = make_mesh((1, 1), ("data", "model"))
    ft = FTConfig(ckpt_dir=str(tmp_path / "ckpt"), ckpt_every=0,
                  heartbeat_path=str(tmp_path / "hb.json"))
    _, losses = train_loop(cfg, steps=2, batch=2, seq=32, mesh=mesh, ft=ft,
                           quiet=True)
    assert len(losses) == 2
    spans = telemetry.registry().snapshot()["histograms"]["span_ms"]
    step_span = spans['span="train.step"']
    assert step_span["count"] == 2 and step_span["sum"] > 0
