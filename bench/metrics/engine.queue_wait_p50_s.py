"""Queue wait: from a request's due time on the open-loop schedule to its
admission (``Request.timeline()["admit"]``), median over the requests due
in the window that were admitted.  Moves ``ttft_p50_s``."""
import statistics


def read(run):
    waits = [tl["admit"] - due for due, tl in run.result["timelines"]
             if "admit" in tl]
    return statistics.median(waits) if waits else None
