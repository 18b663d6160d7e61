"""Roofline share of the Pallas paged-decode kernel (scope
``paged_decode``): the selected and sliding branches' required FLOPs and
bytes for the live decode rows of every traced tick, in every layer, as the
least time the chip could take, over the kernel's device time.  Moves
``itl_p95_ms``."""
from bench import trace as tr
from bench import work


def read(run):
    ns, calls = tr.kernel_ns(run.ops, "paged_decode", run.t0, run.t1)
    pos = [p for _, _, _, dec in run.result["traced_ticks"] for p in dec]
    if ns <= 0 or not pos:
        return None
    f, b = work.paged_decode_work(pos, run.arch)
    n = run.arch["n_layers"]
    share, _ = work.roofline_share(n * f, n * b, ns / 1e9, run.peaks)
    return share
