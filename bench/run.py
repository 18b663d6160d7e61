#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic mix, its correctness limits and
its per-layer metrics are found by name from ``BENCHMARK.json``.  With
``--trace 0`` the result line carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of part of
the window.  The run needs a TPU whose kind is in ``bench/peaks.json``;
anywhere else it exits 1 and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
# the TPU runtime would otherwise keep its logs under a fixed /tmp path
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from bench import spec  # noqa: E402
from bench import trace as tr  # noqa: E402

TRACE_DIR = ROOT / ".bench_trace"
SPAN_LABELS = {"engine.tick", "engine.admit", "engine.prefill_chunk",
               "engine.decode", "engine.host_sync", "bench.window",
               "bench.step"}


class DeviceError(RuntimeError):
    pass


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def check_device(chips: int) -> tuple[dict, dict]:
    """The device as JAX reports it, and its peaks.  No TPU, too few chips
    or a kind without peaks is an error: nothing falls back to the CPU."""
    import jax

    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise DeviceError(f"no TPU: JAX found platform {d.platform!r}")
    if len(devs) < chips:
        raise DeviceError(f"the cell needs {chips} chips, JAX found "
                          f"{len(devs)}")
    try:
        peaks = spec.peaks(d.device_kind)
    except KeyError as e:
        raise DeviceError(str(e)) from None
    return {"platform": d.platform, "kind": d.device_kind,
            "count": chips}, peaks


class Context:
    """What a cell module calls around its window: ``open``/``close`` bracket
    the measured window (compilations inside it are counted), ``start``/
    ``stop`` bracket the traced part (``enabled`` with ``--trace 1``)."""

    def __init__(self, enabled: bool, logdir: pathlib.Path):
        import jax

        self.enabled, self.logdir = enabled, logdir
        self.compiles = 0
        self._window_compiles = None
        self._ann = None
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, secs, **kw):
        if name == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def open(self):
        self._window_compiles = self.compiles

    def close(self) -> int:
        return self.compiles - self._window_compiles

    def start(self):
        import jax

        shutil.rmtree(self.logdir, ignore_errors=True)
        jax.profiler.start_trace(str(self.logdir))
        self._ann = jax.profiler.TraceAnnotation("bench.window")
        self._ann.__enter__()

    def stop(self):
        import jax

        self._ann.__exit__(None, None, None)
        jax.profiler.stop_trace()


def per_layer(cell, result: dict, peaks: dict, logdir) -> tuple:
    """(metrics, device busy/window, breakdown) from the trace."""
    trace = tr.load(str(logdir))
    win = tr.spans(trace.host, "bench.window")
    if len(win) != 1:
        raise RuntimeError(f"expected one bench.window span, got {len(win)}")
    t0, t1 = win[0]
    ops = {k: v for k, v in trace.devices.items()}
    busy = sum(tr.busy_ns(v, t0, t1) for v in ops.values()) / len(ops)
    run = types.SimpleNamespace(
        trace=trace, t0=t0, t1=t1, window_s=(t1 - t0) / 1e9,
        busy_s=busy / 1e9, ops=next(iter(ops.values())), result=result,
        arch=result["arch"], peaks=peaks, cell=cell)
    log(f"[trace] kernels named by scope: {tr.scoped_kernels(run.ops)}")
    metrics = {}
    for m in cell.per_layer:
        v = spec.metric_reader(m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        else:
            log(f"[trace] NOT READ: {m['name']}, listed for this cell, "
                f"found nothing to read; it is left out of the line")
    breakdown = {
        "device_ops": tr.top_ops(run.ops, t0, t1),
        "idle_gaps": tr.idle_gaps(run.ops, trace.host, t0, t1, SPAN_LABELS),
    }
    return metrics, {"busy_s": run.busy_s, "window_s": run.window_s}, \
        breakdown


def end_to_end(cell, result: dict) -> dict:
    values = {"setup_s": result["setup_s"]}
    values.update(result.get("stats", {}))
    if "train_tokens_per_s" in result:
        values["train_tokens_per_s"] = result["train_tokens_per_s"]
    return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end}


def execute(cell, seed: int, seconds: float, trace: bool, device: dict,
            peaks: dict, t_process: float) -> dict:
    """Drive one run of ``cell`` on the device JAX gives; returns the
    result line's object."""
    import importlib

    ctx = Context(trace, TRACE_DIR / f"{cell.name}-{seed}")
    module = importlib.import_module(f"bench.{cell.traffic['kind']}")
    result = module.run(cell, seed, seconds, ctx, t_process, log=log)
    log(f"[bench] set-up {result['setup_s']:.3f} s; compilations inside "
        f"the window: {result['compiles_in_window']}")
    checks = result["checks"]
    stats = result.get("stats", {})
    failed = int(stats.get("failed", 0))
    line = {
        "correct": all(c["ok"] for c in checks) and failed == 0,
        "attempted": int(stats.get("attempted", result.get("steps", 0))),
        "failed": failed,
        "metrics": None,
        "device": dict(device, memory_peak_bytes=result["memory_peak_bytes"]),
    }
    if trace:
        metrics, busy, breakdown = per_layer(cell, result, peaks, ctx.logdir)
        shutil.rmtree(ctx.logdir, ignore_errors=True)
        line["metrics"] = metrics
        line["device"].update(busy)
        line["breakdown"] = breakdown
    else:
        line["metrics"] = end_to_end(cell, result)
    line["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                      for c in checks}
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec.cell(args.workload)
    try:
        device, peaks = check_device(cell.chips)
    except DeviceError as e:
        log(f"bench: {e}")
        return 1
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    log(f"[bench] compile cache: {enable_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    line = execute(cell, args.seed, args.seconds, bool(args.trace), device,
                   peaks, T_PROCESS)
    for name, c in line["checks"].items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
