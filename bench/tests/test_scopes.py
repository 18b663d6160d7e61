"""Layer scopes and span arguments: attribution of op_name paths, the
scope names the benchmark reads against the program's list and against the
lowered step and tick programs, and the counts on the engine's spans in a
profile recorded on the CPU."""
from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import pytest

from bench import scopes
from bench import trace as tr


@pytest.mark.parametrize("op_name,scope,tick,remat", [
    # JAX's wrappers come off each component
    ("jit(f)/transpose(jvp(mlp))/dot_general", "mlp", "", False),
    ("jit(f)/jvp(attn.qkv)/vmap()/tanh", "attn.qkv", "", False),
    # a recompute under jax.checkpoint
    ("jit(f)/transpose(jvp(jvp()))/checkpoint/rematted_computation/mlp/"
     "dot_general", "mlp", "", True),
    # the innermost listed scope wins, and the innermost tick.* is the
    # sub-step (a mixed tick's decode runs inside its tick.prefill)
    ("jit(<lambda>)/tick.prefill/while/body/closed_call/vmap(nsa.select)/"
     "vmap(nsa.index)/jit(argsort)/sort", "nsa.index", "tick.prefill",
     False),
    ("jit(<lambda>)/tick.prefill/while/body/tick.decode/mlp/dot_general",
     "mlp", "tick.decode", False),
    ("jit(<lambda>)/tick.prefill/while/body/dynamic_slice", "tick.prefill",
     "tick.prefill", False),
    ("jit(<lambda>)/while/body/tick.decode/kv.gather/gather", "kv.gather",
     "tick.decode", False),
    # a kernel under layer scopes keeps its kernel's name
    ("jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "vmap(nsa.select)/vmap(fsa_selected_dkv)/pallas_call",
     "fsa_selected_dkv", "", False),
    ("jit(<lambda>)/tick.decode/while/body/nsa.select/paged_decode/"
     "pallas_call", "paged_decode", "tick.decode", False),
    # nothing listed: unclaimed
    ("jit(step)/jvp()/while/body/add", "", "", False),
    ("", "", "", False),
])
def test_attribution(op_name, scope, tick, remat):
    a = scopes.attribute(op_name)
    assert (a.scope, a.tick, a.remat) == (scope, tick, remat)
    if a.scope and "pallas_call" in op_name:
        assert a.scope == tr.scope_kernel(op_name)


def test_names_are_the_programs():
    from repro.telemetry import SCOPES

    assert sorted(scopes.NAMES) == sorted(SCOPES)
    with pytest.raises(ValueError):
        from repro.telemetry import named_scope
        named_scope("not.a.scope")


def test_leaves_drop_containers():
    ops = [(0, 100, "%while.3 = (s32[]) while(%tuple.1), body=%body"),
           (10, 40, "%fusion.1 = f32[8] fusion(%p)"), (12, 20, "copy-done.4"),
           (50, 90, "%custom-call.7 = f32[8] custom-call(%a)"),
           (120, 130, "conditional.2"), (125, 128, "while_fusion.9")]
    # a loop's or branch's own event does not count; what runs in it, or
    # beside a kernel, does
    assert [o[0] for o in scopes.leaves(ops)] == [10, 12, 50, 125]


def _scopes_in(lowered) -> set:
    """The names of NAMES in the op_name locations of a lowered program."""
    text = lowered.as_text(debug_info=True)
    found = set()
    for loc in set(re.findall(r'loc\("([^"]*)"', text)):
        found.update(c for c in scopes.components(loc) if c in scopes.NAMES)
    return found


TRAIN = {"embed", "attn.qkv", "nsa.compress", "nsa.select", "nsa.index",
         "nsa.window", "nsa.gate", "attn.out", "mlp", "lm_head", "optimizer"}
TICK = {"tick.prefill", "tick.decode", "embed", "attn.qkv", "nsa.compress",
        "nsa.select", "nsa.index", "nsa.window", "nsa.gate", "attn.out",
        "kv.gather", "kv.write", "mlp", "lm_head"}


@pytest.fixture(scope="module")
def tiny_cfg():
    from repro.configs import get_config, reduced

    return reduced(get_config("h2o-danube-3-4b"))


@pytest.mark.parametrize("backend", ["fsa", "sparse_union"])
def test_train_step_carries_the_scopes(tiny_cfg, backend):
    import dataclasses

    from repro.launch.steps import make_train_step
    from repro.models import build
    from repro.optim import AdamWConfig, init_opt_state

    cfg = dataclasses.replace(tiny_cfg, attn_impl=backend)
    opt = AdamWConfig()
    params = jax.eval_shape(lambda: build(cfg).init(jax.random.PRNGKey(0)))
    state = jax.eval_shape(lambda p: {"params": p,
                                      "opt": init_opt_state(p, opt)}, params)
    toks = jax.ShapeDtypeStruct((1, 128), jnp.int32)
    lowered = jax.jit(make_train_step(cfg, None, opt)).lower(
        state, {"tokens": toks, "labels": toks})
    assert _scopes_in(lowered) == TRAIN


def _tiny_engine(cfg, **kw):
    from repro.serving import Engine

    return Engine(cfg, n_slots=2, max_len=96, prefill_chunk=32, **kw)


def test_mixed_tick_carries_the_scopes(tiny_cfg):
    eng = _tiny_engine(tiny_cfg)
    b, c = eng.n_slots, eng.prefill_chunk
    zi = jnp.zeros((b,), jnp.int32)
    lowered = eng._mixed.lower(
        eng.params, eng.cache.data, jnp.zeros((b, c), jnp.int32), zi,
        zi + c, zi, zi, jnp.zeros((b,), bool), eng.cache.views())
    assert _scopes_in(lowered) == TICK
    assert set(scopes.NAMES) == TICK | TRAIN


def test_engine_spans_carry_the_rows(tiny_cfg, tmp_path):
    """A profiled mixed tick: rows = slots x chunk, live_rows = the prompt
    rows sent, decode_rows = the slots decoding alongside; then a decode
    tick's rows and live rows."""
    eng = _tiny_engine(tiny_cfg)
    eng.submit(list(range(1, 41)), max_new=8)
    eng.step()                  # the first request prefills 32 of 40
    eng.submit(list(range(1, 21)), max_new=1)
    jax.profiler.start_trace(str(tmp_path))
    eng.step()                  # 8 + 20 prompt rows, nothing decodes yet
    eng.step()                  # the first request decodes, alone
    jax.profiler.stop_trace()
    t = tr.load(str(tmp_path))
    raw = open(next(tmp_path.rglob("*.xplane.pb")), "rb").read()
    spans = scopes.host_spans(raw)
    pf = [a for *_, n, a in spans if n == "engine.prefill_chunk"]
    dec = [a for *_, n, a in spans if n == "engine.decode"]
    assert pf == [{"rows": 2 * 32, "live_rows": 8 + 20, "decode_rows": 0}]
    assert dec == [{"rows": 2, "live_rows": 1}]
    assert len(tr.spans(t.host, "engine.tick")) == 2


def test_program_op_names_fill_what_xla_dropped():
    """A fusion without an op_name, or with the loop's, takes its fused
    computation's root's; a copy or sort takes its operand's, else its
    computation's shared scopes."""
    from bench.tests.test_trace import _pb

    ins = lambda i, name, op="", called=(), operands=(): _pb(
        (1, name), (2, "x"), (35, i),
        *(((7, _pb((2, op))),) if op else ()),
        *((36, o) for o in operands), *((38, c) for c in called))
    fused = _pb((1, "fused"), (5, 2), (6, 21),
                (2, ins(20, "p", "")),
                (2, ins(21, "add.1", "jit(f)/attn.qkv/add")))
    main = _pb((1, "main"), (5, 1),
               (2, ins(10, "x.1", "x")),
               (2, ins(11, "fusion.7", called=(2,), operands=(10,))),
               (2, ins(12, "dot.3", "jit(f)/mlp/dot_general")),
               (2, ins(13, "copy.9", operands=(12,))),
               # stamped with the loop's op_name: dropped as well
               (2, ins(14, "fusion.8", "jit(f)/jvp()/while", called=(2,))),
               (2, ins(15, "sort.2", "jit(f)/jvp()/while", operands=(12,))),
               # nothing better than the stamp of the loop it was made for
               (2, ins(16, "while.3", "jit(f)/tick.prefill/while")),
               (2, ins(17, "copy.4", operands=(10, 16))))
    body = _pb((1, "body"), (5, 3),
               (2, ins(30, "param.1", "args[0]")),
               (2, ins(31, "dot.4", "jit(f)/tick.prefill/while/body/mlp/dot")),
               (2, ins(32, "add.5", "jit(f)/tick.prefill/while/body/add")),
               (2, ins(33, "copy.6", operands=(30,))))
    names = scopes.program_op_names(_pb((1, _pb((1, "m"), (3, fused),
                                                 (3, main), (3, body)))))
    assert names["fusion.7"] == "jit(f)/attn.qkv/add"
    assert names["copy.9"] == "jit(f)/mlp/dot_general"
    assert names["fusion.8"] == "jit(f)/attn.qkv/add"
    assert names["sort.2"] == "jit(f)/mlp/dot_general"
    assert scopes.attribute(names["copy.4"]).scope == "tick.prefill"
    # nothing of its own, its fusion's or its operand's: the scopes every
    # op_name of its computation shares
    assert scopes.attribute(names["copy.6"]).scope == "tick.prefill"


def test_programs_from_a_cpu_trace(tmp_path):
    @jax.jit
    def f(x):
        with jax.named_scope("mlp"):
            return jnp.tanh(x @ x).sum()

    x = jnp.ones((16, 16))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    f(x).block_until_ready()
    jax.profiler.stop_trace()
    raw = open(next(tmp_path.rglob("*.xplane.pb")), "rb").read()
    ops = [op for prog in scopes.programs(raw).values()
           for op in prog.values()]
    assert any(scopes.attribute(op).scope == "mlp" for op in ops)
