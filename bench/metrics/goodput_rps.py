"""Requests that finished within their budget b_total (completion minus
t_gen), over the window's arrival span. A dropped request is a miss: this
is the in-run measure of the paper's Def.-2 capacity."""

UNIT = "req/s"


def read(run):
    ok = sum(1 for r in run.served() if r.e2e <= r.b_total)
    return ok / run.seconds
