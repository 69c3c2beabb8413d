"""chip_smoke.py on the CPU: its kernel and serve phases at glm4-9b's smoke
size (kernels interpreted), and its refusal to run without a TPU."""

import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from repro.configs import get_config

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_kernel_phase_interpreted(chip_smoke):
    chip_smoke.kernel_phase(
        get_config("glm4-9b", smoke=True), interpret=True,
        seqs=(64, 15), batch=3, cache_len=256, rows=64,
    )


def test_serve_phase_smoke(chip_smoke, capsys):
    cfg = get_config("glm4-9b", smoke=True)
    assert cfg.dtype == "bfloat16"
    info = chip_smoke.serve_phase(cfg, max_seq=64)
    out = capsys.readouterr().out
    assert "relative L2 error" in out
    assert info["tokens"] % chip_smoke.N_OUTPUT == 0 and info["tokens"] > 0
    assert "no Pallas kernel" in info["attention"]


@pytest.mark.parametrize("alone", [False, True], ids=["checkout", "alone"])
def test_exits_nonzero_without_tpu(tmp_path, alone):
    """Under JAX_PLATFORMS=cpu the script stops before any work; copied
    into a directory without the rest of the repo it cannot import it."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    script = ROOT / "chip_smoke.py"
    if alone:
        env.pop("PYTHONPATH", None)
        script = Path(shutil.copy(script, tmp_path))
    proc = subprocess.run(
        [sys.executable, str(script)], cwd=script.parent, env=env,
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
