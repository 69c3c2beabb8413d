"""95th percentile over admitted requests of admission (the server clock
when `submit` is entered) minus arrival at the node."""

from benchlib.record import p95

UNIT = "ms"
LAYER = "admission (serving/icc.py)"
MOVES = "ttft_p95_ms"


def read(run):
    v = p95([r.admitted - r.arrival for r in run.requests if r.admitted is not None])
    return None if v is None else v * 1e3
