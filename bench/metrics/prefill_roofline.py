"""Share of its roofline reached by the jitted prefill program: the least
time the traced prefill calls need (the larger of FLOPs over peak FLOP/s
and bytes over peak bandwidth, counted by the configuration's reference
module), over the device time of the "jit_prefill" program runs in the
trace."""

UNIT = "%"
LAYER = "model step (models/transformer.py, jitted prefill and decode)"
MOVES = "ttft_p95_ms"


def read(run):
    tr = run.trace
    if tr is None or not tr.program_runs["prefill"]:
        return None
    return 100.0 * run.least_time("prefill") / tr.program_s["prefill"]
