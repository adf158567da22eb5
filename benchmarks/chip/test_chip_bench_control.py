"""The comparison that decides `correct`, shown to fail where it must.

Each test drives a whole run of the harness, comparison included, with
the timed path broken underneath it, and sees `correct` come out false:
the control (the plain product with W in int8 in the program's place),
the program's own int8 path, a served answer altered where it is
produced, half of each micro-batch left out. The same run with nothing
planted comes out true.

Everything runs on the CPU at a size a test run holds; the limits are the
committed ones (`checks/<workload>.json`).
"""

import json
import os
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import faults  # noqa: E402
import gen  # noqa: E402
import harness  # noqa: E402
import reference  # noqa: E402

SEED = 2 ** 31 + 1234
PEAK = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def load(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def cells():
    return {w["name"]: w for w in harness.load_json(harness.ROOT,
                                                    "BENCHMARK.json")
            ["workloads"]}


def serve_cells():
    return [n for n, w in cells().items()
            if load("traffic", w["traffic"] + ".json")["kind"]
            == "serve_open_loop"]


def small(cfg, **kw):
    """The configuration at a CPU size: every shape law kept, counts cut."""
    out = dict(cfg, n_train=2000, n_features=3000, n_labels=300,
               features=dict(cfg["features"], features_per_point=40))
    out.update(kw)
    return out


def run(cell, seconds):
    w = cells()[cell]
    cfg = small(load("configs", w["config"] + ".json"))
    tf = dict(load("traffic", w["traffic"] + ".json"), rate_rps=40,
              buckets=[1, 2, 4, 8], check_requests=24, warm_seconds=0.1,
              pool_rows=64)
    import jax
    return harness.run_cell(cell, SEED, seconds, False,
                            devices=jax.devices(), t_start=time.monotonic(),
                            cfg=cfg, traffic=tf, peak=PEAK)


def test_the_reference_judged_against_itself_is_exact():
    cell = serve_cells()[0]
    w = cells()[cell]
    cfg = small(load("configs", w["config"] + ".json"))
    k = load("traffic", w["traffic"] + ".json")["k"]
    x = gen.make_request_pool(cfg, 64, SEED)
    ref = reference.serve_reference(cfg, SEED, x)
    labels, scores = reference.reference_topk(ref.scores, k)
    assert reference.judge_answers(labels, scores, ref, k) == {
        "bad": 0, "score_err": 0.0, "score_err_mean": 0.0, "rank_gap": 0.0}


@pytest.mark.parametrize("cell", serve_cells())
def test_sound_run_reads_correct(cell):
    assert run(cell, 0.5)["correct"] is True


@pytest.mark.parametrize("cell", serve_cells())
@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_control_and_faults_read_incorrect(cell, fault, monkeypatch):
    faults.FAULTS[fault](monkeypatch.setattr)
    res = run(cell, 0.5)
    assert res["correct"] is False
    if fault in faults.CONTROLS:
        # The control fails on the precision of its scores, not on
        # anything the comparison could not judge.
        assert res["checks"]["score_err_mean"]["value"] > \
            res["checks"]["score_err_mean"]["limit"]
        assert res["checks"]["bad_answers"]["value"] == 0
