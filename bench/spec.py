"""Resolve a cell of ``BENCHMARK.json`` to its files, by name.

A configuration is ``configs[*].file``; a traffic mix is
``bench/traffic/<traffic>.json``; a cell's correctness limits are
``bench/limits/<cell>.json``; a per-layer metric is
``bench/metrics/<metric>.py``.  Adding a cell or a metric adds files and
entries; nothing here names one.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict        # the configuration file's contents
    traffic: dict       # the traffic file's contents
    limits: dict        # the correctness limits file's contents
    end_to_end: list    # BENCHMARK.json entries this cell reports
    per_layer: list


def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _reports(metric: dict, cell: str, e2e_names: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves", metric["name"]) in e2e_names


def cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    bench = load_benchmark(root)
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = work[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    names = {m["name"] for m in e2e}
    return Cell(
        name=name, chips=int(w["chips"]),
        config=json.loads((root / conf["file"]).read_text()),
        traffic=json.loads((BENCH / "traffic" / f"{w['traffic']}.json")
                           .read_text()),
        limits=json.loads((BENCH / "limits" / f"{name}.json").read_text()),
        end_to_end=e2e,
        per_layer=[m for m in bench["per_layer"]
                   if _reports(m, name, names)])


def metric_reader(name: str):
    """The ``read(run)`` function of ``bench/metrics/<name>.py``."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; a kind not in the table is an error."""
    table = json.loads((BENCH / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(table)}")
    return table[device_kind]


def arch_of(conf: dict) -> dict:
    """Widths and NSA settings of a configuration file, in the names
    ``bench.work``, ``bench.weights`` and ``bench.reference`` use."""
    vocab, pad = conf["vocab_size"], conf["vocab_pad_to"]
    a = {
        "d_model": conf["hidden_size"],
        "n_heads": conf["num_attention_heads"],
        "n_kv_heads": conf["num_key_value_heads"],
        "head_dim": conf["head_dim"],
        "d_ff": conf["intermediate_size"],
        "vocab": vocab,
        "padded_vocab": -(-vocab // pad) * pad,
        "n_layers": conf["num_hidden_layers"],
        "qkv_bias": bool(conf["attention_bias"]),
        "norm_eps": float(conf["rms_norm_eps"]),
        "rope_theta": float(conf["rope_theta"]),
    }
    a.update({k: int(v) for k, v in conf["nsa"].items()})
    return a


def program_config(conf: dict):
    """The program's ``ModelConfig`` for this configuration: its own preset
    with the file's ``program.overrides``, checked against the file's
    widths so that the two cannot drift apart."""
    import dataclasses as dc

    from repro.configs import get_config
    from repro.core.nsa_config import NSAConfig

    prog = conf["program"]
    cfg = dc.replace(get_config(prog["arch"]), nsa=NSAConfig(**conf["nsa"]),
                     **prog.get("overrides", {}))
    a = arch_of(conf)
    got = {"d_model": cfg.d_model, "n_heads": cfg.n_heads,
           "n_kv_heads": cfg.n_kv_heads, "head_dim": cfg.hd(),
           "d_ff": cfg.d_ff, "vocab": cfg.vocab,
           "padded_vocab": cfg.padded_vocab(), "n_layers": cfg.n_layers,
           "qkv_bias": cfg.use_qkv_bias, "norm_eps": cfg.norm_eps,
           "rope_theta": cfg.rope_theta, "mlp": cfg.mlp,
           "attention": cfg.attention, "dtype": cfg.dtype}
    want = {k: a[k] for k in got if k in a}
    want.update(mlp="swiglu", attention="nsa", dtype=conf["torch_dtype"])
    bad = {k: (got[k], want[k]) for k in want if got[k] != want[k]}
    if bad:
        raise ValueError(f"program config differs from the file: {bad}")
    return cfg
