"""Share of the requests sent that admission dropped as infeasible
(ServeStats.n_dropped), in percent."""

UNIT = "%"
LAYER = "admission (serving/icc.py)"
MOVES = "goodput_rps"


def read(run):
    return 100.0 * run.dropped / run.sent if run.sent else None
