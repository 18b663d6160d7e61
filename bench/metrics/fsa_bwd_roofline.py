"""Roofline share of the FSA selected-branch backward (scopes
``fsa_selected_dq`` and ``fsa_selected_dkv``, one pair per backward): the
required work of five matmuls per (query head, selected key) and one pass
over the tensors, summed over the invocations, over the two kernels'
device time.  Moves ``train_tokens_per_s``."""
from bench import trace as tr
from bench import work


def read(run):
    ns_q, calls = tr.kernel_ns(run.ops, "fsa_selected_dq", run.t0, run.t1)
    ns_kv, _ = tr.kernel_ns(run.ops, "fsa_selected_dkv", run.t0, run.t1)
    if ns_q + ns_kv <= 0:
        return None
    f, b = work.fsa_bwd_work(run.result["seq_len"], run.arch)
    k = calls * run.result["batch"]
    return work.roofline_share(k * f, k * b, (ns_q + ns_kv) / 1e9,
                               run.peaks)[0]
