"""Pallas TPU kernels. None is on the served path: the model runs the jnp
attention and norms of repro.models, and the kernels are compiled and
checked on their own (tests/test_kernels.py, chip_smoke.py).

Each kernel ships two layers:
  <name>.py — pl.pallas_call + BlockSpec VMEM tiling (TPU target)
  ref.py    — pure-jnp oracle (allclose ground truth)
"""

from . import ref
from .decode_attention import decode_attention
from .flash_attention import flash_attention
from .rmsnorm import rmsnorm

__all__ = ["ref", "flash_attention", "decode_attention", "rmsnorm"]
