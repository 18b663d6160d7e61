"""The work the serving metrics count is read from the tick programs the
engine dispatched: over a whole run, the prefill spans cover every prompt
once and the decode positions cover every token after the first."""
from __future__ import annotations

from bench import serve, traffic
from bench.tests import tiny


def test_tick_work_covers_every_prompt_and_token():
    cell = tiny.serve_cell()
    srv = serve.Server(cell, 4000000011)
    mix = dict(cell.traffic, rate_per_s=50.0)
    reqs = [srv.eng.submit(r.prompt, max_new=r.max_new)
            for r in traffic.serve_schedule(mix, 0.1, 5, srv.a["vocab"])]
    first = len(srv.tlog.ticks)
    serve._run_until(srv.eng, srv.tlog, lambda: False, 600)
    work = [serve.tick_work(t) for t in srv.tlog.ticks[first:]]
    spans = [s for _, _, sp, _ in work for s in sp]
    decoded = [p for _, _, _, dec in work for p in dec]
    assert len(reqs) >= 3 and all(r.done for r in reqs)
    assert sum(b - a for a, b, _ in spans) == sum(r.prompt_len for r in reqs)
    assert sorted(b for a, b, last in spans if last) == sorted(
        r.prompt_len for r in reqs)
    # the first token comes from prefill; each later one from a decode row
    # at the position of the token before it
    assert sorted(decoded) == sorted(
        r.prompt_len + j for r in reqs for j in range(len(r.out) - 1))
