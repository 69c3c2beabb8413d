"""Share of its roofline reached by the jitted decode program: the least
time the traced decode steps need, with K/V read at the slots' real
lengths (not max_seq), over the device time of the "jit_decode" program
runs in the trace."""

UNIT = "%"
LAYER = "model step (models/transformer.py, jitted prefill and decode)"
MOVES = "tpot_p95_ms"


def read(run):
    tr = run.trace
    if tr is None or not tr.program_runs["decode"]:
        return None
    return 100.0 * run.least_time("decode") / tr.program_s["decode"]
