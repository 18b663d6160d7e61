"""Roofline share of the FSA selected-branch forward kernel (scope
``fsa_selected``): each invocation's required work from the step's shapes
(every sequence of the batch), summed over the invocations in the trace,
so that recomputation under remat counts as the work it is; over the
kernel's device time.  Moves ``train_tokens_per_s``."""
from bench import trace as tr
from bench import work


def read(run):
    ns, calls = tr.kernel_ns(run.ops, "fsa_selected", run.t0, run.t1)
    if ns <= 0:
        return None
    f, b = work.fsa_fwd_work(run.result["seq_len"], run.arch)
    k = calls * run.result["batch"]
    return work.roofline_share(k * f, k * b, ns / 1e9, run.peaks)[0]
