"""Model FLOPs of all the window's prefill and decode calls over their
summed host time times the chip's peak bf16 FLOP/s, in percent: the share
of the peak the served steps reach when the queue never empties."""

UNIT = "%"
LAYER = "whole step"
MOVES = "goodput_rps"


def read(run):
    fp, tp = run.prefill_flops()
    fd, td = run.decode_flops()
    t = tp + td
    return 100.0 * (fp + fd) / (t * run.peaks["bf16_flops_per_s"]) if t else None
