"""Model FLOPs of the window's decode steps (active slots, real context)
over their summed host time times the chip's peak bf16 FLOP/s, in
percent."""

UNIT = "%"
LAYER = "whole step"
MOVES = "tpot_p95_ms"


def read(run):
    f, t = run.decode_flops()
    return 100.0 * f / (t * run.peaks["bf16_flops_per_s"]) if t else None
