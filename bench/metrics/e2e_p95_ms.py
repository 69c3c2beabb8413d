"""95th percentile over the requests served in the window of completion
(last token) minus t_gen, on the server's clock: the latency the paper's
budget b_total bounds."""

from benchlib.record import p95

UNIT = "ms"


def read(run):
    v = p95([r.e2e for r in run.served()])
    return None if v is None else v * 1e3
