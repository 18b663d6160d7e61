"""BENCHMARK.json is well formed and every entry resolves to its files by
name; the command refuses a machine without a TPU, or with a TPU whose
kind has no peaks, and prints no result there."""
from __future__ import annotations

import json
import os
import re
import subprocess
import sys
import types

import pytest

from bench import spec

NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.-]{1,16}")
WIDTH_KEYS = re.compile(r".*(_dim|_rank)$|hidden_size|intermediate_size|"
                        r"num_attention_heads|num_key_value_heads")

BENCH = spec.load_benchmark()


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    for word in BENCH["command"]:
        assert not word.startswith("/") and ".." not in word


def test_names_units_and_keys():
    names = []
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        names.append(c["name"])
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        names += [w["name"], w["traffic"]]
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.fullmatch(m["name"]) and UNIT.fullmatch(m["unit"])
        assert m["better"] in ("lower", "higher")
    for n in names:
        assert NAME.fullmatch(n), n
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert "setup_s" in {m["name"] for m in BENCH["end_to_end"]}


def test_every_cell_resolves():
    for w in BENCH["workloads"]:
        cell = spec.cell(w["name"])
        e2e = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell.per_layer, w["name"]
        assert cell.traffic["kind"] in ("serve", "train")
        for m in cell.per_layer:
            assert m["moves"] in e2e
            assert callable(spec.metric_reader(m["name"]))


def test_per_layer_entries():
    cells = {w["name"] for w in BENCH["workloads"]}
    layers = {}
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert set(m["workloads"]) <= cells
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())


def test_config_files():
    files = set()
    for c in BENCH["configs"]:
        assert c["file"].startswith("bench/") and c["file"] not in files
        files.add(c["file"])
        conf = json.loads((spec.ROOT / c["file"]).read_text())
        assert conf["reduced"] == c["reduced"]
        for key in c["reduced"]:
            assert key in conf and not WIDTH_KEYS.fullmatch(key)
        spec.program_config(conf)          # widths agree with the program


def _fake_jax(monkeypatch, platform, kind):
    import jax

    dev = types.SimpleNamespace(platform=platform, device_kind=kind)
    monkeypatch.setattr(jax, "devices", lambda *a: [dev])


@pytest.mark.parametrize("platform,kind", [("cpu", "cpu"),
                                           ("tpu", "TPU v9 imaginary")])
def test_run_refuses_without_a_known_tpu(monkeypatch, capsys, platform,
                                         kind):
    sys.path.insert(0, str(spec.BENCH))
    import run

    _fake_jax(monkeypatch, platform, kind)
    name = BENCH["workloads"][0]["name"]
    assert run.main(["--workload", name, "--seed", "1", "--seconds", "1",
                     "--trace", "0"]) == 1
    assert capsys.readouterr().out == ""


def test_command_exits_nonzero_on_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    name = BENCH["workloads"][0]["name"]
    p = subprocess.run(BENCH["command"] + ["--workload", name, "--seed",
                                           "3000000000", "--seconds", "1",
                                           "--trace", "0"],
                       cwd=spec.ROOT, env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""
