"""95th percentile over the requests served in the window of (last token -
first token) / (n_output - 1): the decode steps, with the prefills that
interleave with them, on the server's clock."""

from benchlib.record import p95

UNIT = "ms"


def read(run):
    v = p95([r.tpot for r in run.served()])
    return None if v is None else v * 1e3
