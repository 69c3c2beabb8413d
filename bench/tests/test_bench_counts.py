"""FLOP and byte counts of a served call at smoke shapes, as the
reference module of the dense block family gives them, against a count
made by hand."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchlib import spec  # noqa: E402
from benchlib.record import least_time  # noqa: E402

ref = spec.reference_module("dense_decoder")

# d 8, 2 query heads of 4 over 1 kv head, d_ff 16, vocab 32, 2 layers
GATED = {"n_layers": 2, "d_model": 8, "n_heads": 2, "n_kv_heads": 1,
         "head_dim": 4, "d_ff": 16, "vocab_size": 32, "qkv_bias": True,
         "mlp": "gated_silu", "rope_theta": 1e4, "norm_eps": 1e-5,
         "tie_embeddings": False, "dtype": "bfloat16"}
RELU2 = dict(GATED, qkv_bias=False, mlp="relu2")


def test_layer_params_by_hand():
    # q 8*2*4=64, k 8*1*4=32, v 32, o 2*4*8=64 -> 192; mlp 3*8*16=384
    assert ref.layer_matmul_params(GATED) == 192 + 384
    # + two gains of 8 + bias (2+1+1)*4 = 16
    assert ref.layer_params(GATED) == 576 + 16 + 16
    assert ref.layer_matmul_params(RELU2) == 192 + 256
    assert ref.layer_params(RELU2) == 448 + 16


def test_prefill_by_hand():
    flops, nbytes = ref.prefill_counts(GATED, 3)
    # per layer: 2*576*3 matmul, attention 4*H*dh*(1+2+3) = 4*2*4*6 = 192
    # head: one row, 2*8*32 = 512
    assert flops == 2 * (2 * 576 * 3 + 192) + 512
    # weights: 2 layers * 608 + head 256 + final norm 8 + 3 rows * 8
    # kv written: 3 tokens * 2 layers * (k, v) * 1 head * 4 dims; bf16
    assert nbytes == 2 * (2 * 608 + 256 + 8 + 24) + 2 * (3 * 2 * 2 * 4)


def test_decode_by_hand():
    flops, nbytes = ref.decode_counts(RELU2, (5, 0))
    # two slots: matmuls 2*448*2 per layer, attention over 6 + 1 keys
    assert flops == 2 * (2 * 448 * 2 + 4 * 2 * 4 * 7) + 2 * 8 * 32 * 2
    # weights 2*464 + 256 + 8 + 2 rows * 8; kv read 5 cached, written 2
    kv_tok = 2 * 2 * 1 * 4
    assert nbytes == 2 * (2 * 464 + 256 + 8 + 16) + 2 * (5 + 2) * kv_tok


def test_least_time_takes_the_larger_bound():
    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert least_time(1000.0, 50.0, peaks) == 10.0
    assert least_time(100.0, 50.0, peaks) == 5.0


def test_peaks_table_knows_the_v5e_and_refuses_other_kinds():
    p = spec.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16e9
    for kind in ("cpu", "TPU v4", "TPU v6 lite"):
        with pytest.raises(spec.SpecError):
            spec.peaks(kind)
