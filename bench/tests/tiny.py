"""Tiny cells for CPU tests: the benchmark's own files at small widths, so
the cell modules, the reference and the metric readers run end to end without
a chip (Pallas kernels run in interpret mode there)."""
from __future__ import annotations

import copy
import json

from bench import spec

NSA = {"block_size": 16, "num_selected": 4, "cmp_block_size": 8,
       "cmp_stride": 4, "window_size": 32, "num_init_blocks": 1,
       "num_local_blocks": 2, "min_seq_for_sparse": 1}

WIDTHS = {"hidden_size": 64, "num_attention_heads": 4,
          "num_key_value_heads": 2, "head_dim": 16,
          "intermediate_size": 128, "vocab_size": 256,
          "num_hidden_layers": 2}

PROGRAM = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
           "head_dim": 16, "d_ff": 128, "vocab": 256}


def config(name: str, *, bias: bool = False, train: bool = False) -> dict:
    conf = json.loads((spec.ROOT / next(
        c["file"] for c in spec.load_benchmark()["configs"]
        if c["name"] == name)).read_text())
    conf = copy.deepcopy(conf)
    conf.update(WIDTHS, nsa=dict(NSA), attention_bias=bias,
                torch_dtype="bfloat16")
    over = dict(PROGRAM, use_qkv_bias=bias)
    if train:
        over["attn_impl"] = "auto"
    conf["program"] = {"arch": conf["program"]["arch"], "overrides": over}
    return conf


def serve_cell(**limits) -> spec.Cell:
    traffic = {"kind": "serve", "n_slots": 2, "max_len": 128,
               "prefix_cache": False, "arrivals": "poisson",
               "rate_per_s": 4.0,
               "prompt_len": {"dist": "uniform", "min": 20, "max": 60},
               "output_len": {"dist": "uniform", "min": 3, "max": 6},
               "warmup_prompt_len": 20, "warmup_output_len": 2,
               "drain_s": 60, "trace_seconds": 0.5, "preroll_s": 1.0}
    lim = {"served_logit_gap": 0.05, "min_tokens_checked": 4,
           "sample_requests": 2, "ref_seq_len": 128, "ref_rows": 8}
    lim.update(limits)
    return spec.Cell("tiny-serve", 1, config("h2o-danube-3-4b"), traffic,
                     lim, [], [])


def train_cell(**limits) -> spec.Cell:
    traffic = {"kind": "train", "batch": 1, "seq_len": 64,
               "trace_after_steps": 1, "trace_steps": 1}
    lim = {"grad_norm_gap": 1e-2, "change_norm_gap": 1e-2}
    lim.update(limits)
    conf = config("h2o-danube-3-4b.train", train=True)
    conf["torch_dtype"] = "bfloat16"
    return spec.Cell("tiny-train", 1, conf, traffic, lim, [], [])


class NoTrace:
    enabled = False

    def open(self):
        pass

    def close(self):
        return 0

    def start(self):
        pass

    def stop(self):
        pass
