"""Mean host time of `engine.step` over all the window's decode steps,
taken by the benchmark's engine proxy; each step waits for the device."""

UNIT = "ms"
LAYER = "engine (serving/engine.py)"
MOVES = "tpot_p95_ms"


def read(run):
    v = [t for t, _, _ in run.decode_calls]
    return 1e3 * sum(v) / len(v) if v else None
