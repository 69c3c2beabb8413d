"""Share of the traced window in which no operation ran on the device: 1 -
union of the device's op intervals / the window, in percent."""

UNIT = "%"
LAYER = "device (TPU v5e)"
MOVES = "e2e_p95_ms"


def read(run):
    tr = run.trace
    return None if tr is None else 100.0 * tr.idle_share
