"""The trace reduction on intervals counted by hand, kernels named by the
scope in their metadata or else by their HLO instruction, and a small trace
recorded on the CPU (host spans only: the CPU has no TPU plane)."""
from __future__ import annotations

import time

import pytest

from bench import trace as tr

# device operations (ns) and their kernels: a loop [0, 100) holding two
# ops, a kernel after a gap, and a kernel that runs past the window's end
OPS = [
    (0, 100, "%while.3 = (s32[]) while(...)", ""),
    (10, 40, "%fusion.12 = bf16[8] fusion(...)", ""),
    (50, 90, "%vmap_fsa_selected_.21 = (bf16[8]) custom-call(...)",
     "fsa_selected"),
    (130, 170, "%custom-call.12 = (f32[8]) custom-call(...)", "paged_decode"),
    (190, 260, "%vmap_fsa_selected_dq_.10 = f32[8] custom-call(...)",
     "fsa_selected_dq"),
]
HOST = [
    (0, 250, "engine.tick"),
    (100, 180, "engine.host_sync"),
    (180, 250, "engine.admit"),
]


def test_union_and_busy():
    assert tr.union(OPS) == [(0, 100), (130, 170), (190, 260)]
    # window [0, 200): 100 + 40 + 10
    assert tr.busy_ns(OPS, 0, 200) == 150


def test_kernel_names():
    assert tr.kernel_ns(OPS, "fsa_selected", 0, 200) == (40.0, 1)
    assert tr.kernel_ns(OPS, "paged_decode", 0, 200) == (40.0, 1)
    # clipped at the window's end
    assert tr.kernel_ns(OPS, "fsa_selected_dq", 0, 200) == (10.0, 1)
    # a custom call is named by its metadata, not its instruction
    assert tr.kernel_ns(OPS, "custom-call", 0, 300) == (0.0, 0)
    assert tr.scoped_kernels(OPS) == {"fsa_selected": 1, "paged_decode": 1,
                                      "fsa_selected_dq": 1}


@pytest.mark.parametrize("op_name,kernel", [
    ("jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "vmap(fsa_selected_dkv)/pallas_call", "fsa_selected_dkv"),
    ("jit(train_step)/jvp()/closed_call/vmap(vmap(fsa_selected))/"
     "pallas_call", "fsa_selected"),
    ("jit(<lambda>)/while/body/paged_decode/pallas_call", "paged_decode"),
    ("jit(train_step)/jvp()/while/body/closed_call/vmap()/gather", ""),
])
def test_scope_from_op_name(op_name, kernel):
    assert tr.scope_kernel(op_name) == kernel


def _pb(*fields) -> bytes:
    """A protobuf message of (field number, int | bytes | str) fields."""
    def varint(x):
        out = b""
        while True:
            out += bytes([(x & 0x7F) | (0x80 if x > 0x7F else 0)])
            x >>= 7
            if not x:
                return out
    out = b""
    for f, v in fields:
        if isinstance(v, int):
            out += varint(f << 3) + varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += varint(f << 3 | 2) + varint(len(v)) + v
    return out


def test_kernels_from_the_event_metadata():
    """A device plane whose event metadata carries the op_name as a string
    and as a reference to an interned string; the plane's lines are
    skipped."""
    op = ("jit(step)/transpose(jvp())/checkpoint/vmap(fsa_selected_dkv)/"
          "pallas_call")
    ev = lambda i, name, *stats: _pb((4, _pb((1, i), (2, _pb(
        (1, i), (2, name), (4, name.split(" = ")[0][1:]),
        *((5, st) for st in stats))))))
    stat_meta = lambda i, name: _pb((5, _pb((1, i), (2, _pb((1, i),
                                                          (2, name))))))
    plane = _pb((2, "/device:TPU:0"), (3, _pb((2, "XLA Ops")))) \
        + ev(1, "%custom-call.7 = f32[8] custom-call()", _pb((1, 9), (5, op))) \
        + ev(2, "%custom-call.8 = f32[8] custom-call()", _pb((1, 9), (7, 10))) \
        + ev(3, "%fusion.1 = f32[8] fusion()", _pb((1, 9), (5, "jit(f)/add"))) \
        + stat_meta(9, "tf_op") \
        + stat_meta(10, "jit(f)/paged_decode/pallas_call")
    host = _pb((2, "/host:CPU"))
    assert tr.event_kernels(_pb((1, plane), (1, host))) == {
        "/device:TPU:0": {
            "%custom-call.7 = f32[8] custom-call()": "fsa_selected_dkv",
            "custom-call.7": "fsa_selected_dkv",
            "%custom-call.8 = f32[8] custom-call()": "paged_decode",
            "custom-call.8": "paged_decode"},
        "/host:CPU": {}}


def test_self_time():
    assert tr.self_ns((0, 250), [(100, 180), (120, 130)]) == 170


def test_idle_gaps_labelled_by_innermost_open_span():
    gaps = dict(tr.idle_gaps(OPS, HOST, 0, 300, {"engine.tick",
                                                 "engine.host_sync",
                                                 "engine.admit"}))
    # idle: [100,130) in host_sync, [170,190) in admit, [260,300) none
    assert gaps == pytest.approx({"engine.host_sync": 30e-9,
                                  "engine.admit": 20e-9, "none": 40e-9})


def test_top_ops_skip_loops():
    top = dict(tr.top_ops(OPS, 0, 300))
    assert "while.3" not in top
    assert top["fsa_selected"] == pytest.approx(40e-9)
    assert top["fusion.12"] == pytest.approx(30e-9)


def test_cpu_recorded_trace(tmp_path):
    import jax

    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(2):
            with jax.profiler.TraceAnnotation("engine.tick"):
                time.sleep(0.02)
                with jax.profiler.TraceAnnotation("engine.host_sync"):
                    time.sleep(0.03)
    jax.profiler.stop_trace()
    t = tr.load(str(tmp_path))
    (w0, w1), = tr.spans(t.host, "bench.window")
    ticks = tr.spans(t.host, "engine.tick", w0, w1)
    syncs = tr.spans(t.host, "engine.host_sync", w0, w1)
    assert len(ticks) == 2 and len(syncs) == 2
    for tick in ticks:
        assert 0.049 <= (tick[1] - tick[0]) / 1e9 < 0.2
        assert 0.019 <= tr.self_ns(tick, syncs) / 1e9 < 0.1
    assert t.devices == {}
