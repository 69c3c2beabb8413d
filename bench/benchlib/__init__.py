"""The chip benchmark's own code: finding cells by name, traffic, weights,
the engine proxy, trace reduction and the correctness check. Nothing here is imported by the program under test."""

import os

# libtpu logs to /tmp/tpu_logs unless told otherwise; a run writes only
# inside its checkout and its own HOME and TMPDIR
os.environ.setdefault("TPU_LOG_DIR", "disabled")
