"""Model FLOP/s utilization of the engine ticks in the traced window: the
forward FLOPs the live rows required (prompt rows prefilled, tokens
decoded, NSA's three branches at each row's position; the LM head only
where a token is produced) over the ticks' summed wall time times the
chip's peak.  Padded rows and repeated work do not count.  Moves
``itl_p95_ms``."""
from bench import work


def read(run):
    ticks = run.result["traced_ticks"]
    wall = sum(t1 - t0 for t0, t1, _, _ in ticks)
    if not ticks or wall <= 0:
        return None
    flops = sum(work.serve_flops(spans, dec, run.arch)
                for _, _, spans, dec in ticks)
    return 100.0 * flops / (wall * run.peaks["bf16_flops_per_s"])
