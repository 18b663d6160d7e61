"""Model FLOP/s utilization of the traced training steps: 6 x matmul
params per token plus NSA attention at 3 x its forward, at the required
work (recomputation does not count), over the traced window times the
chip's peak.  Moves ``train_tokens_per_s``."""
from bench import work


def read(run):
    r = run.result
    if not r["traced_steps"]:
        return None
    flops = r["traced_steps"] * r["batch"] * work.train_flops(r["seq_len"],
                                                              run.arch)
    return 100.0 * flops / (run.window_s * run.peaks["bf16_flops_per_s"])
