"""The harness around the cells: BENCHMARK.json against the contract and
the files it names, the trace reduction, and the refusal to run without a
TPU."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench import check, run, trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SPEC = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELLS = [w["name"] for w in SPEC["workloads"]]
METRICS = SPEC["end_to_end"] + SPEC["per_layer"]


def test_benchmark_json_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]
    assert 1 <= SPEC["run_seconds"] <= 51
    names = [m["name"] for m in METRICS]
    assert len(names) == len(set(names))
    assert "setup_s" in names


@pytest.mark.parametrize("m", METRICS, ids=lambda m: m["name"])
def test_metric_entry(m):
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    assert set(m["workloads"]) <= set(CELLS) if "workloads" in m else True
    if m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    else:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        e2e = {e["name"]: e for e in SPEC["end_to_end"]}
        moved = e2e[m["moves"]]
        assert set(m.get("workloads", CELLS)) <= set(
            moved.get("workloads", CELLS))
        assert os.path.isfile(os.path.join(ROOT, "bench", "metrics",
                                           m["name"] + ".py"))


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files(cell):
    c = run.load_cell(cell)
    w = c["cell"]
    assert NAME.match(w["name"]) and w["chips"] in (1, 4)
    assert 1 <= len(w["why"]) <= 200
    assert c["config"]["chips"] == w["chips"]
    assert c["config"]["name"] == w["config"]
    e2e = {m["name"] for m in c["end_to_end"]}
    assert {"setup_s", "points_per_s"} <= e2e and c["per_layer"]
    assert set(c["limits"]) and all(
        isinstance(v, (int, float)) for v in c["limits"].values())


@pytest.mark.parametrize("entry", SPEC["configs"], ids=lambda c: c["name"])
def test_config_entry(entry):
    with open(os.path.join(ROOT, entry["file"])) as f:
        cfg = json.load(f)
    assert entry["file"].startswith("bench/")
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]
    assert any(w["config"] == entry["name"] for w in SPEC["workloads"])
    assert cfg["guarantees"]


def test_judge_holds_each_number_to_its_limit():
    ok, out = check.judge({"a": 0.5, "b": 2.0}, {"a": 1.0, "b": 1.0})
    assert not ok and out == {"a": {"value": 0.5, "limit": 1.0},
                              "b": {"value": 2.0, "limit": 1.0}}
    assert check.judge({"a": 1.0}, {"a": 1.0})[0]
    assert not check.judge({}, {"a": 1.0})[0]
    assert not check.judge({"a": float("nan")}, {"a": 1.0})[0]


def _synthetic():
    # device 0 busy [0,10) and [15,20) of the window [0, 40); device 1
    # busy [5, 25) (two overlapping ops); host annotations label the gaps
    return {"devices": {
        "/device:TPU:0": [["fusion.1", 0.0, 10e9], ["fusion.2", 15e9, 5e9]],
        "/device:TPU:1": [["fusion.1", 5e9, 15e9], ["copy.3", 20e9, 5e9]]},
        "host": [["bench.window", 0.0, 40e9],
                 ["bench.diversify", 0.0, 30e9],
                 ["bench.readback", 25e9, 5e9],
                 ["bench.generate", 30e9, 10e9]]}


def test_trace_reduce_synthetic():
    red = trace_reduce.reduce(_synthetic())
    assert red["window_s"] == 40.0
    assert red["busy_per_device"] == {"/device:TPU:0": 15.0,
                                      "/device:TPU:1": 20.0}
    assert red["busy_s"] == 17.5
    assert red["idle_pct"] == pytest.approx(100 * (1 - 17.5 / 40))
    assert red["device_ops"][0] == ["fusion.1", 12.5]
    # a gap goes to the shortest annotation covering its midpoint.  Device
    # 0 is idle [10,15) (diversify) and [20,40) (midpoint 30: readback);
    # device 1 is idle [0,5) (diversify) and [25,40) (32.5: generate).
    # Seconds are per device.
    gaps = dict(red["idle_gaps"])
    assert gaps == pytest.approx({"bench.diversify": 5.0,
                                  "bench.readback": 10.0,
                                  "bench.generate": 7.5})


def test_trace_reduce_recorded():
    """20 ms of a traced embed-stream window, recorded on one TPU v5e: 411
    device ops, the host's bench.* annotations around them."""
    with open(os.path.join(HERE, "data", "trace_small.json")) as f:
        small = json.load(f)
    red = trace_reduce.reduce(small)
    assert red["window_s"] == pytest.approx(0.02, rel=1e-9)
    assert red["busy_s"] == pytest.approx(0.004022474, rel=1e-9)
    assert red["idle_pct"] == pytest.approx(79.88763, rel=1e-9)
    assert red["device_ops"][0] == ["fusion.7", pytest.approx(0.002391567)]
    assert [g[0] for g in red["idle_gaps"]][:2] == ["bench.engine",
                                                   "bench.generate"]
    # the same busy time from a 100 ns grid, independent of the reduction
    import numpy as np

    (lo, width), = [(h[1], h[2]) for h in small["host"]
                    if h[0] == "bench.window"]
    grid = np.zeros(int(width // 100), bool)
    for _, s, d in small["devices"]["/device:TPU:0"]:
        a = max(0, int((s - lo) // 100))
        b = min(grid.size, int(np.ceil((s + d - lo) / 100)))
        grid[a:b] = True
    assert grid.sum() * 100e-9 == pytest.approx(red["busy_s"], abs=1e-5)
    gaps = sum(v for _, v in red["idle_gaps"])
    assert gaps == pytest.approx(red["window_s"] - red["busy_s"])


def test_trace_reduce_needs_a_device_op():
    with pytest.raises(ValueError):
        trace_reduce.reduce({"devices": {"/device:TPU:0": []}, "host": []})


def _bench_env():
    return {"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu",
            "HOME": os.environ.get("HOME", "/tmp")}


def test_run_refuses_cpu():
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "embed-stream",
         "--seed", str(2 ** 33 + 1), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=_bench_env(), capture_output=True, text=True,
        timeout=300)
    assert proc.returncode != 0
    assert "{" not in proc.stdout
    assert "needs a TPU" in proc.stderr


def test_run_refuses_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "embed-stream",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=_bench_env(), capture_output=True, text=True,
        timeout=300)
    assert proc.returncode != 0 and "{" not in proc.stdout


def test_peaks_known_and_unknown():
    p = run.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 8.19e11 and p["bf16_flops_per_s"] == 1.97e14
    assert p["source"]
    with pytest.raises(KeyError):
        run.peaks("cpu")


def test_run_refuses_unknown_cell():
    assert run.main(["--workload", "no-such-cell", "--seed", "1",
                     "--seconds", "1"]) != 0
