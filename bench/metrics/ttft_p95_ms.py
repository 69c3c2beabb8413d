"""95th percentile over the requests served in the window of first token
minus arrival at the compute node (t_gen + t_comm): admission wait plus
prefill, on the server's clock."""

from benchlib.record import p95

UNIT = "ms"


def read(run):
    v = p95([r.ttft for r in run.served()])
    return None if v is None else v * 1e3
