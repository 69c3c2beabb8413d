"""Model FLOPs of the window's prefill calls over their summed host time
times the chip's peak bf16 FLOP/s, in percent: the whole prefill step's
share of the peak, kernels, host work and waits included."""

UNIT = "%"
LAYER = "whole step"
MOVES = "ttft_p95_ms"


def read(run):
    f, t = run.prefill_flops()
    return 100.0 * f / (t * run.peaks["bf16_flops_per_s"]) if t else None
