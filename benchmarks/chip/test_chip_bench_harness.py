"""The harness off the chip: refusals, the result line, BENCHMARK.json."""

import json
import os
import re
import shutil
import subprocess
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)

import counts  # noqa: E402
import harness  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def run_py(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "eurlex-4k.serve-bsr", "--seed", str(2 ** 31 + 5), "--seconds", "1"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_refuses_without_a_tpu():
    r = run_py(ROOT, {"PYTHONPATH": os.path.join(ROOT, "src")})
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "TPU" in r.stderr


def test_refuses_where_only_the_benchmark_is(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in bench()["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    r = run_py(str(tmp_path), {"PYTHONPATH": ""})
    assert r.returncode != 0
    assert r.stdout.strip() == ""


def test_check_devices_needs_tpus_and_enough_of_them():
    cpu = types.SimpleNamespace(platform="cpu", device_kind="cpu")
    tpu = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    with pytest.raises(harness.NoChip):
        harness.check_devices([cpu], 1)
    with pytest.raises(harness.NoChip):
        harness.check_devices([], 1)
    with pytest.raises(harness.NoChip):
        harness.check_devices([tpu], 4)
    assert harness.check_devices([tpu] * 4, 1) == [tpu]


def test_unknown_device_kind_raises():
    assert counts.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        counts.peaks("TPU v99 imaginary")
    with pytest.raises(KeyError):
        counts.peaks("cpu")


def test_benchmark_json_keys_names_and_units():
    b = bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= b["run_seconds"] <= 51
    names = set()
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] not in names
        names.add(c["name"])
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        assert all(NAME.match(k) for k in c["reduced"])
    cells = set()
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in names and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200 and "\n" not in w["why"]
        assert os.path.isfile(os.path.join(HERE, "traffic",
                                           w["traffic"] + ".json"))
        assert os.path.isfile(os.path.join(HERE, "checks",
                                           w["name"] + ".json"))
        cells.add(w["name"])
    metrics = set()
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and m["name"] not in metrics
        metrics.add(m["name"])
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= cells
    for m in b["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    e2e = {m["name"] for m in b["end_to_end"]}
    assert "setup_s" in e2e
    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e
        assert os.path.isfile(os.path.join(HERE, "layers",
                                           m["name"] + ".py"))
    for cell in cells:
        e, layer = harness.cell_metrics(b, cell)
        assert "setup_s" in {m["name"] for m in e} and len(e) >= 2
        assert layer


def test_result_line_has_the_keys_the_driver_reads(capsys):
    result = {"correct": True, "attempted": 3, "failed": 0,
              "metrics": {"setup_s": {"value": 1.5, "unit": "s"}},
              "device": {"platform": "tpu", "kind": "TPU v5 lite",
                         "count": 1, "memory_peak_bytes": 7},
              "checks": {"score_err": {"value": 1e-7, "limit": 1e-5}}}
    harness.emit(result)
    out, err = capsys.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "checks"
    assert err.strip().splitlines()[-1].startswith("check score_err:")
