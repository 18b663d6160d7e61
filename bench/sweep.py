#!/usr/bin/env python3
"""Find a serving cell's knee once, by a sweep of fixed rates on the chip.

    python3 bench/sweep.py --workload <cell> --rates 0.2,0.3,0.5 --seconds 40

One engine, warmed once, serves an open-loop window at each rate in turn
(the traffic file's mix with its rate replaced), draining between rates.
For each rate it prints the requests due and finished, the queue's depth
over the window, TTFT and inter-token gap.  The knee is the highest rate
whose queue does not grow over the window; the cell's file then takes a
fixed rate below it.  Not part of a benchmark run.
"""
from __future__ import annotations

import time

T_PROCESS = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

import run as bench_run  # noqa: E402  (puts the repo on sys.path)
from bench import serve, spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=40)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    cell = spec.cell(args.workload)
    try:
        bench_run.check_device(cell.chips)
    except bench_run.DeviceError as e:
        bench_run.log(f"sweep: {e}")
        return 1
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    srv = serve.Server(cell, args.seed)
    srv.warm_up(cell.traffic, args.seed)
    for k, rate in enumerate(float(r) for r in args.rates.split(",")):
        mix = dict(cell.traffic, rate_per_s=rate, drain_s=0)
        w = srv.window(mix, args.seconds, args.seed + k,
                       bench_run.Context(False, bench_run.TRACE_DIR))
        depth = w["depth"]
        half = [d for t, d in depth if t < args.seconds / 2]
        late = [d for t, d in depth if t >= args.seconds / 2]
        st = w["stats"]
        print(json.dumps({
            "rate_per_s": rate, "due": st["attempted"],
            "finished": sum(1 for r in w["reqs"] if r.done),
            "no_first_token": st["failed"],
            "queue_mean_first_half": sum(half) / max(len(half), 1),
            "queue_mean_second_half": sum(late) / max(len(late), 1),
            "queue_at_close": depth[-1][1] if depth else 0,
            "ttft_p50_s": st["ttft_p50_s"], "itl_p95_ms": st["itl_p95_ms"],
            "output_tokens_per_s": st["output_tokens_per_s"],
            "ticks": len(w["ticks"])}), flush=True)
        srv.drain()
    return 0


if __name__ == "__main__":
    sys.exit(main())
