"""Process start to window start: imports, the seeded weights, the
program's calibration, warm-up, and compilation or compile-cache loads."""

UNIT = "s"


def read(run):
    return run.setup_s
