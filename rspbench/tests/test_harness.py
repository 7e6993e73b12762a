"""The harness finds every piece by name, keeps to the result contract, and
refuses to run without a TPU."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest
import tiny

import harness

BENCH = harness.load_json(os.path.join(tiny.ROOT, "BENCHMARK.json"))
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.mark.parametrize("name", CELLS)
def test_every_cell_loads_by_name(name):
    cell = harness.load_cell(name, BENCH)
    assert cell.chips in (1, 4)
    assert os.path.exists(cell.driver_path)
    assert cell.limits, "a cell compares at least one number"
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer, "a cell reports at least one per-layer metric"
    for m in cell.per_layer:
        assert m["moves"] in names
        mod = harness.load_module(os.path.join(tiny.BENCH, "metrics", f"{m['name']}.py"), m["name"])
        assert callable(mod.read)


def test_benchmark_json_keeps_to_the_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("rspbench/")
        assert 1 <= len(c["source"]) <= 200 and 1 <= len(c["why"]) <= 200
        cfg = harness.load_json(os.path.join(tiny.ROOT, c["file"]))
        assert cfg["name"] == c["name"]
        assert any(w["config"] == c["name"] for w in BENCH["workloads"])
    pairs = {(w["config"], w["traffic"]) for w in BENCH["workloads"]}
    assert len(pairs) == len(BENCH["workloads"])
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and len(w["why"]) <= 200
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace") and 0.01 <= m["bound"] <= 0.25
    layers = {m["layer"] for m in BENCH["per_layer"]}
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert m["layer"] in layers and set(m["workloads"]) <= set(CELLS)
    assert len(json.dumps(BENCH)) < 64 * 1024


def _run_py(args, cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run([sys.executable, *args], cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=300)


def _no_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        if line.startswith("{") and '"correct"' in line:
            return False
    return True


def test_cpu_only_run_fails_without_a_result():
    p = _run_py(["rspbench/run.py", "--workload", CELLS[0], "--seed", "2147483999",
                 "--seconds", "1", "--trace", "0"], cwd=tiny.ROOT)
    assert p.returncode != 0
    assert _no_result(p.stdout)
    assert "TPU" in p.stderr


def test_checkout_without_the_program_fails_without_a_result(tmp_path):
    shutil.copytree(tiny.BENCH, tmp_path / "rspbench",
                    ignore=shutil.ignore_patterns(".jax_cache", "__pycache__"))
    shutil.copy(os.path.join(tiny.ROOT, "BENCHMARK.json"), tmp_path)
    code = ("import sys, time; sys.path.insert(0, 'rspbench'); import harness; "
            f"sys.exit(harness.main(['--workload', {CELLS[0]!r}, '--seed', '1', '--seconds', '1'],"
            " t0=time.perf_counter(), require_tpu=False))")
    p = _run_py(["-c", code], cwd=str(tmp_path))
    assert p.returncode != 0
    assert _no_result(p.stdout)
    assert "repro" in p.stderr
