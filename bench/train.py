"""Training cells: the program's jitted train step (``launch.steps.
make_train_step``, donated as ``launch.train.train_loop`` runs it, without
checkpoints) on weights and optimizer state made from the seed.

Set-up builds the one compiled step with its state and drives it through
its first three steps by the window's own call and feed; those steps are
what the reference checks.  The window then runs further steps until
``--seconds`` have passed and ends on ``block_until_ready``.

Correctness, once the program's state is freed: ``bench.reference`` runs
the same three steps in float32 with the configuration's AdamW.  Compared:
each step's loss; each leaf's norm of the first gradient as the optimizer
got it (read back from Adam's first moment after one step); each leaf's
norm of the parameters' change after three steps.  A leaf's gap is
|program norm - reference norm| over the larger of the reference leaf's
norm and the median leaf's; the worst leaf is compared.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from bench import reference, traffic, weights
from bench.spec import arch_of, program_config

CHECK_STEPS = 3


def leaf_norms(tree) -> dict:
    import jax
    import jax.numpy as jnp

    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    f = jax.jit(lambda xs: [jnp.sqrt(jnp.sum(jnp.square(x.astype(
        jnp.float32)))) for x in xs])
    vals = f([x for _, x in flat])
    return {jax.tree_util.keystr(p): float(v) for (p, _), v in zip(flat, vals)}


def change_norms(master, init) -> dict:
    import jax
    import jax.numpy as jnp

    diff = jax.jit(lambda m, p: jax.tree.map(
        lambda a, b: a - b.astype(jnp.float32), m, p))(master, init)
    return leaf_norms(diff)


def optimizer(conf: dict):
    from repro.optim import AdamWConfig
    return AdamWConfig(**conf["optimizer"])


def build(cell, seed: int):
    """The compiled step, its state made from the seed, and the feed."""
    import jax

    from repro.launch.steps import make_train_step
    from repro.optim import init_opt_state

    conf, mix = cell.config, cell.traffic
    a = arch_of(conf)
    opt_cfg = optimizer(conf)
    state = jax.jit(lambda p: {"params": p, "opt": init_opt_state(
        p, opt_cfg)})(weights.make(a, seed))
    step = jax.jit(make_train_step(program_config(conf), None, opt_cfg),
                   donate_argnums=(0,))
    return step, state, traffic.train_batch_fn(mix, a["vocab"], seed)


def first_steps(cell, seed: int, step, state, batch):
    """Drive the step through its first three steps; returns the state and
    the program's readings: losses, the first gradient's leaf norms as
    Adam's first moment holds it, the parameters' change."""
    import jax

    a, b1 = arch_of(cell.config), optimizer(cell.config).b1
    losses, grad_n = [], None
    for i in range(CHECK_STEPS):
        state, m = step(state, batch(i))
        losses.append(float(m["loss"]))
        if i == 0:
            g = jax.jit(lambda t: jax.tree.map(lambda x: x / (1 - b1), t))(
                state["opt"]["m"])
            grad_n = leaf_norms(g)
            del g
    init = weights.make(a, seed)
    chg_n = change_norms(state["opt"]["master"], init)
    del init
    return state, {"loss": losses, "grad": grad_n, "change": chg_n}


def run(cell, seed: int, seconds: float, ctx, t_process: float,
        log=print) -> dict:
    import jax

    conf, mix = cell.config, cell.traffic
    a = arch_of(conf)
    t_start = time.time()
    step, state, batch = build(cell, seed)
    jax.block_until_ready(state)
    t_built = time.time()
    tokens_per_step = int(mix["batch"]) * int(mix["seq_len"])
    state, prog = first_steps(cell, seed, step, state, batch)
    jax.block_until_ready(state)
    setup_s = time.time() - t_process
    log(f"[train] set-up: process and JAX start {t_start - t_process:.3f} s,"
        f" weights and optimizer state {t_built - t_start:.3f} s, compile "
        f"and the {CHECK_STEPS} checked steps "
        f"{time.time() - t_built:.3f} s")

    n = CHECK_STEPS
    trace_at = n + int(mix["trace_after_steps"])
    trace_end = trace_at + int(mix["trace_steps"])
    pending = None
    ctx.open()
    t0 = time.time()
    while True:
        if ctx.enabled and n in (trace_at, trace_end):
            jax.block_until_ready(state)
            ctx.start() if n == trace_at else ctx.stop()
        with jax.profiler.TraceAnnotation("bench.step"):
            state, m = step(state, batch(n))
        n += 1
        if pending is not None:
            pending.block_until_ready()
        pending = m["loss"]
        if time.time() - t0 >= seconds and (not ctx.enabled or n > trace_end):
            break
    jax.block_until_ready(state)
    t1 = time.time()
    compiles = ctx.close()
    steps = n - CHECK_STEPS
    log(f"[train] window {t1 - t0:.3f} s: {steps} steps of "
        f"{tokens_per_step} tokens, last loss {float(m['loss']):.4f}")
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    del state, m, pending
    gc.collect()

    ref = reference_readings(a, seed, batch, optimizer(conf),
                             conf["schedule"], reference.Num(False), log)
    checks = compare(prog, ref, cell.limits, log)
    return {
        "setup_s": setup_s, "window_s": t1 - t0, "steps": steps,
        "train_tokens_per_s": steps * tokens_per_step / (t1 - t0),
        "memory_peak_bytes": peak, "checks": checks, "arch": a,
        "tokens_per_step": tokens_per_step,
        "traced_steps": int(mix["trace_steps"]) if ctx.enabled else 0,
        "compiles_in_window": compiles,
        "seq_len": int(mix["seq_len"]), "batch": int(mix["batch"]),
    }


def reference_readings(a, seed, batch, opt_cfg, sched, num, log,
                       keep_tokens: float = 1.0) -> dict:
    """The first three steps in ``bench.reference``: losses, first-gradient
    leaf norms after clipping, parameter-change leaf norms.  ``keep_tokens``
    below 1 plants a fault: the loss's mean is taken over that leading
    share of each sequence's tokens only."""
    import jax
    import jax.numpy as jnp

    t = time.time()
    f32 = jax.jit(lambda p: jax.tree.map(lambda x: x.astype(jnp.float32), p))
    items = tuple(sorted(a.items()))
    vg = jax.jit(lambda p, b: jax.value_and_grad(
        lambda q: reference.loss(q, b, dict(items), num))(p))
    adam = jax.jit(lambda p, g, m, v, k: adamw(p, g, m, v, k, opt_cfg,
                                               sched),
                   donate_argnums=(0, 1, 2, 3))
    p = f32(weights.make(a, seed))
    m = jax.tree.map(jnp.zeros_like, p)
    v = jax.tree.map(jnp.zeros_like, p)
    losses, grad_n = [], None
    for i in range(CHECK_STEPS):
        b = batch(i)
        if keep_tokens < 1.0:
            n = b["labels"].shape[1]
            b = dict(b, labels=b["labels"].at[:, int(n * keep_tokens):]
                     .set(-1))
        loss, g = vg(p, b)
        losses.append(float(loss))
        p, m, v, g_used = adam(p, g, m, v, jnp.asarray(i, jnp.int32))
        if i == 0:
            grad_n = leaf_norms(g_used)
        del g_used
    del m, v
    chg_n = change_norms(p, weights.make(a, seed))
    log(f"[check] reference {CHECK_STEPS} steps in {time.time() - t:.1f} s")
    return {"loss": losses, "grad": grad_n, "change": chg_n}


def adamw(p, g, m, v, step, c, sched):
    """AdamW as the configuration states it: global-norm clipping, bias
    correction, decoupled weight decay, and the program's learning-rate
    schedule (linear warm-up, then cosine), all in float32.  Returns the
    new (p, m, v) and the gradient after clipping."""
    import jax
    import jax.numpy as jnp

    gn = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(g)))
    clip = jnp.minimum(1.0, c.grad_clip / (gn + 1e-9)) if c.grad_clip > 0 \
        else 1.0
    s = step.astype(jnp.float32)
    warm, total = float(sched["warmup"]), float(sched["total"])
    floor = float(sched["min_ratio"])
    prog = jnp.clip((s - warm) / (total - warm), 0.0, 1.0)
    lr = c.lr * jnp.minimum(s / warm, 1.0) * (
        floor + (1 - floor) * 0.5 * (1 + jnp.cos(jnp.pi * prog)))
    t = s + 1
    b1c, b2c = 1 - c.b1 ** t, 1 - c.b2 ** t
    g = jax.tree.map(lambda x: x * clip, g)
    m = jax.tree.map(lambda a, b: c.b1 * a + (1 - c.b1) * b, m, g)
    v = jax.tree.map(lambda a, b: c.b2 * a + (1 - c.b2) * b * b, v, g)
    p = jax.tree.map(lambda x, a, b: x - lr * (
        (a / b1c) / (jnp.sqrt(b / b2c) + c.eps) + c.weight_decay * x),
        p, m, v)
    return p, m, v, g


def leaf_gap(prog: dict, ref: dict, keep=None) -> tuple[float, str]:
    """Worst leaf's |prog - ref| / max(ref leaf, median ref leaf)."""
    keys = [k for k in ref if keep is None or k in keep]
    med = float(np.median([ref[k] for k in keys]))
    worst = max(keys, key=lambda k: abs(prog[k] - ref[k]) / max(ref[k], med))
    return abs(prog[worst] - ref[worst]) / max(ref[worst], med), worst


def readings(prog: dict, ref: dict) -> dict:
    loss = max(abs(x - y) / abs(y) for x, y in zip(prog["loss"], ref["loss"]))
    gmed = float(np.median(list(ref["grad"].values())))
    moved = {k for k, v in ref["grad"].items() if v >= 1e-3 * gmed}
    grad, gleaf = leaf_gap(prog["grad"], ref["grad"])
    chg, cleaf = leaf_gap(prog["change"], ref["change"], moved)
    return {"loss_rel_gap": loss, "grad_norm_gap": grad,
            "change_norm_gap": chg, "grad_leaf": gleaf, "change_leaf": cleaf,
            "excluded_leaves": sorted(set(ref["grad"]) - moved)}


def compare(prog, ref, limits, log) -> list:
    """The numbers the cell's limits file names; the loss gap is logged
    (no control or fault separates it from sound runs, PERF.md)."""
    r = readings(prog, ref)
    log(f"[check] loss gap {r['loss_rel_gap']:.3e} (not compared); worst "
        f"leaves: grad {r['grad_leaf']}, change {r['change_leaf']}; left "
        f"out of the change (reference gradient under 1e-3 of the median "
        f"leaf's): {r['excluded_leaves']}")
    return [{"name": k, "value": r[k], "limit": limits[k],
             "ok": bool(r[k] <= limits[k])}
            for k in ("grad_norm_gap", "change_norm_gap")]
