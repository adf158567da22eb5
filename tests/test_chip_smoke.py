"""chip_smoke.py: refuses to run without a TPU, and its one- and four-chip
paths run end to end on the CPU at a tiny size (interpret-mode kernels)."""

import os
import shutil
import subprocess
import sys
import textwrap

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")

TINY = dict(n_train=300, n_test=100, n_features=2048, n_labels=256,
            label_batch=128, n_requests=6, max_rows=3, buckets=(1, 2, 4, 8),
            p1_floor=0.5)


def _run(args, cwd, env_extra=None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **(env_extra or {})}
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


@pytest.mark.parametrize("where", ["repo", "alone"])
def test_refuses_without_tpu(tmp_path, where):
    """On the CPU, and in a directory holding nothing of the repo but the
    script, it exits non-zero and prints no result."""
    script = SMOKE
    if where == "alone":
        script = str(tmp_path / "chip_smoke.py")
        shutil.copy(SMOKE, script)
    r = _run([script], cwd=os.path.dirname(script))
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    if where == "repo":
        assert r.returncode == 2 and "no TPU" in r.stderr


def test_one_chip_path_tiny(tmp_path, monkeypatch):
    monkeypatch.syspath_prepend(REPO)
    import chip_smoke as cs
    cs.run_one_chip(cs.SmokeConfig(**TINY), 0, str(tmp_path))


def test_four_chip_path_tiny(tmp_path):
    """Four virtual CPU devices; the CPU reports no allocator peaks, so the
    per-device memory floor is satisfied by stubbing the reader."""
    code = textwrap.dedent(f"""
        import sys
        sys.path.insert(0, {REPO!r})
        import chip_smoke as cs
        cs.peak_bytes = lambda device: 1 << 40
        cs.run_four_chips(cs.SmokeConfig(**{TINY!r}), 0, {str(tmp_path)!r})
        print("DONE")
    """)
    r = _run(["-c", code], cwd=REPO, env_extra={
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4"})
    assert r.returncode == 0 and "DONE" in r.stdout, r.stdout + r.stderr


@pytest.mark.parametrize("fault", ["none", "zeros", "half of X"])
def test_stopping_check_catches_a_wrong_solve(tmp_path, monkeypatch, fault):
    """The four-chip weight check passes a real solve and fails a model
    that was never solved or was solved on half of the training rows."""
    monkeypatch.syspath_prepend(REPO)
    import dataclasses
    import chip_smoke as cs

    cfg = cs.SmokeConfig(**TINY)
    data = cs.make_xmc_dataset(n_train=cfg.n_train, n_test=cfg.n_test,
                               n_features=cfg.n_features,
                               n_labels=cfg.label_batch, seed=0)
    solver = cs.SolverSpec(delta=0.0)
    spec = cs.XMCSpec(solver=solver, schedule=cs.ScheduleSpec(
        label_batch=cfg.label_batch, block_shape=(128, 128)))

    def solve(d, name):
        return cs.dense_w(cs.fit(d.X_train, d.Y_train, spec,
                                 str(tmp_path / name)).model()[0])

    Ws = {"ref": solve(data, "ref")}
    if fault == "none":
        Ws["other"] = solve(data, "again")
    elif fault == "zeros":
        Ws["other"] = np.zeros_like(Ws["ref"])
    else:
        half = cfg.n_train // 2
        Ws["other"] = solve(dataclasses.replace(
            data, X_train=data.X_train[:half],
            Y_train=data.Y_train[:half]), "half")
    if fault == "none":
        cs.check_solutions(data, Ws, solver, "ref")
    else:
        with pytest.raises(cs.CheckFailed, match="misses the stopping rule"):
            cs.check_solutions(data, Ws, solver, "ref")
