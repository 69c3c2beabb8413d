"""Mean GenResult.prefill_s of the requests served in the window: the
engine's own host time of a prefill, its first token and the splice."""

UNIT = "ms"
LAYER = "engine (serving/engine.py)"
MOVES = "ttft_p95_ms"


def read(run):
    v = run.prefill_s_program
    return 1e3 * sum(v) / len(v) if v else None
