#!/usr/bin/env python3
"""Readings that set a cell's correctness limits, on the chip at the cell's
own size.  Not part of a benchmark run.

    python3 bench/control.py --workload <cell> --seeds 11,12,... \
        --control-seeds 11,12,13 [--seconds 20]

For each seed, the program's reading of every number the cell compares
(a sound run).  For each control seed, the same numbers with the control
in the program's place: the reference computed with every matmul operand
in float8 (the precision below the configuration's bfloat16).  Training
cells also read the fault "half of the batch left out" (planted in the
reference: the loss's mean over the first half of the tokens); a step that
leaves the state unchanged reads 1 by construction and needs no run.
Prints one JSON line per reading; the limits file takes a value above the
largest sound reading and below the smallest control reading.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys

import numpy as np

import run as bench_run  # noqa: F401  (puts the repo on sys.path)
from bench import reference, serve, spec, train


class _Quiet:
    enabled = False

    def open(self):
        pass

    def close(self):
        return 0


def serve_readings(cell, seed: int, seconds: float, control: bool) -> dict:
    srv = serve.Server(cell, seed)
    srv.warm_up(cell.traffic, seed)
    # the pre-roll steadies timings only; the numbers compared do not need it
    w = srv.window(dict(cell.traffic, drain_s=600, preroll_s=0), seconds,
                   seed, _Quiet())
    srv.drain()
    finished = [r for r in w["reqs"] if r.done and r.out]
    reqs = serve.sample(finished, int(cell.limits["sample_requests"]), seed)
    params, a = srv.params, srv.a
    srv.eng.cache.data = None
    del srv
    gc.collect()
    lim = cell.limits
    f32 = serve.served_gaps(params, a, reqs, reference.Num(False),
                            int(lim["ref_seq_len"]), int(lim["ref_rows"]))
    out = {"served_logit_gap": serve.widest_gap(f32),
           "tokens": sum(len(t) for _, t in f32)}
    if control:
        f8 = serve.served_gaps(params, a, reqs, reference.Num(True),
                               int(lim["ref_seq_len"]), int(lim["ref_rows"]))
        out["control_served_logit_gap"] = serve.widest_gap(
            [(z, np.argmax(z8, -1)) for (z, _), (z8, _) in zip(f32, f8)])
    return out


def train_readings(cell, seed: int, control: bool) -> dict:
    import jax

    conf = cell.config
    a, opt, sched = spec.arch_of(conf), train.optimizer(conf), \
        conf["schedule"]
    step, state, batch = train.build(cell, seed)
    state, prog = train.first_steps(cell, seed, step, state, batch)
    del state, step
    gc.collect()
    jax.clear_caches()
    log = lambda m: print(m, file=sys.stderr, flush=True)
    ref = train.reference_readings(a, seed, batch, opt, sched,
                                   reference.Num(False), log)
    out = train.readings(prog, ref)
    if control:
        f8 = train.reference_readings(a, seed, batch, opt, sched,
                                      reference.Num(True), log)
        out["control"] = train.readings(f8, ref)
        half = train.reference_readings(a, seed, batch, opt, sched,
                                        reference.Num(False), log,
                                        keep_tokens=0.5)
        out["fault_half_batch"] = train.readings(half, ref)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=20)
    args = ap.parse_args(argv)
    cell = spec.cell(args.workload)
    try:
        bench_run.check_device(cell.chips)
    except bench_run.DeviceError as e:
        bench_run.log(f"control: {e}")
        return 1
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    ctrl = {int(s) for s in args.control_seeds.split(",") if s}
    for seed in (int(s) for s in args.seeds.split(",")):
        if cell.traffic["kind"] == "serve":
            r = serve_readings(cell, seed, args.seconds, seed in ctrl)
        else:
            r = train_readings(cell, seed, seed in ctrl)
        print(json.dumps({"seed": seed, **r}), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
