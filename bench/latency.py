"""End-to-end serving arithmetic from the harness's own stamps.

Each request carries its due time on the open-loop schedule and the host
time of every token it delivered (stamped in the engine's ``on_token``).
Nothing here is a median of pieces: every request and every gap in the
window counts.
"""
from __future__ import annotations

import dataclasses
import statistics


@dataclasses.dataclass
class Served:
    due: float                      # absolute host time it was due
    tokens: list                    # host time of each delivered token
    sent: float | None = None       # when the harness submitted it


def quantile(values, q: float) -> float:
    """Linear-interpolated quantile (q in [0, 1]) of all values."""
    v = sorted(values)
    if not v:
        raise ValueError("no values")
    x = q * (len(v) - 1)
    i = int(x)
    return v[i] if i + 1 >= len(v) else v[i] + (v[i + 1] - v[i]) * (x - i)


def summarize(reqs: list[Served], t0: float, t1: float) -> dict:
    """Window [t0, t1) metrics over the requests due in it.

    ttft_p50_s: median, over every request due in the window, of first
    token minus due time (a request that never delivered is counted as
    failed, not timed); itl_p95_ms: 95th percentile of every gap between
    consecutive tokens of one request whose later token fell in the window;
    output_tokens_per_s: tokens delivered in the window over its length.
    """
    due = [r for r in reqs if t0 <= r.due < t1]
    ttft = [r.tokens[0] - r.due for r in due if r.tokens]
    gaps = [b - a for r in reqs for a, b in zip(r.tokens, r.tokens[1:])
            if t0 <= b < t1]
    out = sum(1 for r in reqs for t in r.tokens if t0 <= t < t1)
    late = [r.sent - r.due for r in due if r.sent is not None]
    return {
        "attempted": len(due),
        "failed": len(due) - len(ttft),
        "ttft_p50_s": statistics.median(ttft) if ttft else None,
        "itl_p95_ms": 1e3 * quantile(gaps, 0.95) if gaps else None,
        "output_tokens_per_s": out / (t1 - t0),
        "n_gaps": len(gaps),
        "send_late_p50_ms": 1e3 * statistics.median(late) if late else None,
        "send_late_max_ms": 1e3 * max(late) if late else None,
    }
