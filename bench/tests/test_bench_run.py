"""bench/run.py refuses to run off a TPU or without the program, and every
cell, configuration, mix and metric of BENCHMARK.json resolves to a file
of its own."""

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))

from benchlib import harness, spec, traffic  # noqa: E402

ARGS = ["--workload", "glm4_ar_steady", "--seed", "3000000001",
        "--seconds", "1", "--trace", "0"]


def _result_lines(stdout: str):
    out = []
    for line in stdout.splitlines():
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        if isinstance(obj, dict) and "correct" in obj:
            out.append(obj)
    return out


def test_run_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(BENCH, "run.py"), *ARGS],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0
    assert not _result_lines(p.stdout)
    assert "TPU" in p.stderr


def test_run_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=tmp_path,
                       env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert not _result_lines(p.stdout)


def test_unknown_cell_is_refused():
    with pytest.raises(spec.SpecError):
        spec.cell("no_such_cell")
    with pytest.raises(spec.SpecError):
        spec.config("../configs/glm4-9b-l20")


BENCHMARK = spec.benchmark()
# every cell and configuration file, also those no entry of BENCHMARK.json
# names yet (PERF.md, open questions)
CELL_FILES = sorted(p.stem for p in (spec.BENCH_DIR / "workloads").glob("*.json"))
CONFIG_FILES = sorted(p.stem for p in (spec.BENCH_DIR / "configs").glob("*.json"))


@pytest.mark.parametrize("name", CELL_FILES)
def test_each_cell_resolves_and_its_program_runs_what_its_file_states(name):
    wl = spec.workload(name)
    conf, mix = spec.config(wl["config"]), spec.traffic(wl["traffic"])
    harness.program_config(conf)  # raises where the sizes differ
    assert wl["why"] and mix["why"]
    # the sequence the engine holds fits its cache
    assert mix["n_input"] + mix["n_output"] <= wl["max_seq"]
    if name not in {w["name"] for w in BENCHMARK["workloads"]}:
        return
    cell = spec.cell(name)
    assert cell.chips == 1
    assert any(m.name == "setup_s" for m in cell.end_to_end)
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    n = traffic.n_requests(cell.traffic, BENCHMARK["run_seconds"])
    assert n == round(cell.traffic["rate_rps"] * BENCHMARK["run_seconds"])


@pytest.mark.parametrize("name", CONFIG_FILES)
def test_each_config_file_matches_benchmark_json(name):
    conf = spec.config(name)
    assert conf["name"] == name
    for key in conf["reduced"]:
        assert key in conf["config"]
    spec.reference_module(conf["reference"])
    for entry in BENCHMARK["configs"]:
        if entry["name"] == name:
            assert entry["file"] == f"bench/configs/{name}.json"
            assert conf["reduced"] == entry["reduced"]
            assert conf["source"] == entry["source"]


@pytest.mark.parametrize("kind", ["end_to_end", "per_layer"])
def test_each_metric_has_a_reader_that_agrees_with_benchmark_json(kind):
    for m in BENCHMARK[kind]:
        mod = spec.metric_module(m["name"])
        assert mod.UNIT == m["unit"], m["name"]
        assert callable(mod.read)
        if kind == "per_layer":
            assert mod.LAYER == m["layer"] and mod.MOVES == m["moves"], m["name"]
            # every cell that reads it reports the end-to-end metric it moves
            for cell in m.get("workloads", [w["name"] for w in BENCHMARK["workloads"]]):
                assert any(e["name"] == m["moves"]
                           and cell in e.get("workloads", [cell])
                           for e in BENCHMARK["end_to_end"]), (m["name"], cell)


def test_traffic_is_the_same_work_in_another_order_for_every_seed():
    mix = spec.traffic("ar_steady")
    a = traffic.generate(mix, 1, 10.0, 1000)
    b = traffic.generate(mix, 2**40 + 3, 10.0, 1000)
    c = traffic.generate(mix, 1, 10.0, 1000)
    assert len(a) == len(b) == round(10.0 * mix["rate_rps"])

    def gaps(rs):
        return np.sort(np.diff([0.0] + [r.t_gen for r in rs]))

    np.testing.assert_allclose(gaps(a), gaps(b), rtol=1e-9)
    assert sorted(r.t_comm for r in a) == pytest.approx(sorted(r.t_comm for r in b))
    assert a[-1].t_gen == pytest.approx(10.0)
    assert [r.t_gen for r in a] != [r.t_gen for r in b]

    # the same requests (gap, t_comm), in another order of at most 8 runs
    def pairs(rs):
        g = np.diff([0.0] + [r.t_gen for r in rs])
        return [(round(x, 6), round(r.t_comm, 9)) for x, r in zip(g, rs)]

    pa, pb = pairs(a), pairs(b)
    assert sorted(pa) == sorted(pb)
    follows = {(x, y) for x, y in zip(pa, pa[1:])}
    assert sum((x, y) in follows for x, y in zip(pb, pb[1:])) >= len(pb) - 8
    assert all((x.prompt == y.prompt).all() and x.t_gen == y.t_gen
               for x, y in zip(a, c))
    lo, hi = mix["t_comm_s"]
    assert all(lo <= r.t_comm <= hi and len(r.prompt) == mix["n_input"] for r in a)


def test_knee_is_the_highest_rate_below_the_first_that_fails():
    from sweep import knee

    # 5.0 passes by chance above the failing 4.0 and does not count
    assert knee([(3.0, 1.0), (4.0, 0.9125), (5.0, 0.95), (6.0, 0.88)]) == 3.0
    assert knee([(2.0, 0.97), (1.0, 1.0), (2.5, 0.95)]) == 2.5  # any order
    assert knee([(1.0, 0.5), (2.0, 0.99)]) is None
