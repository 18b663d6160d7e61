"""Share of the prefill rows the engine dispatched that held prompt
tokens: the ``live_rows`` over the ``rows`` arguments of the
``engine.prefill_chunk`` spans in the traced window (rows = slots x chunk
of the tick program).  Moves ``itl_p95_ms``."""
from bench import scopes


def read(run):
    spans = [a for *_, a in scopes.of(run).host("engine.prefill_chunk")
             if "rows" in a and "live_rows" in a]
    rows = sum(a["rows"] for a in spans)
    return 100.0 * sum(a["live_rows"] for a in spans) / rows if rows else None
