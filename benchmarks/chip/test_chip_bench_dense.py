"""The dense-query cell off the chip: its generator, its streamed
reference, and the comparison that decides `correct`.

Everything runs on the CPU at a size a test run holds: the configuration
keeps its widths (768 features, 128 x 128 blocks) and its laws, with the
label count cut; the limits are the committed ones
(`checks/<workload>.json`).
"""

import json
import os
import sys
import time

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import faults  # noqa: E402
import gen_dense  # noqa: E402
import harness  # noqa: E402
import reference  # noqa: E402
import reference_dense  # noqa: E402

CELL = "lf-amazontitles-1.3m.serve-bsr"
SEED = 2 ** 31 + 4321
PEAK = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def load(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def cell():
    return {w["name"]: w for w in harness.load_json(
        harness.ROOT, "BENCHMARK.json")["workloads"]}[CELL]


def small(**kw):
    """The configuration with 1,000 labels (8 row blocks, the last one
    padded): every width and law kept."""
    return dict(load("configs", cell()["config"] + ".json"), n_labels=1000,
                n_train=20000, **kw)


def test_dense_queries_are_seeded_unit_rows_with_the_published_labels():
    cfg = load("configs", cell()["config"] + ".json")
    a = gen_dense.dense_queries(cfg, 512, SEED)
    b = gen_dense.dense_queries(cfg, 512, SEED)
    np.testing.assert_array_equal(a.x, b.x)
    assert not np.array_equal(a.x, gen_dense.dense_queries(cfg, 512,
                                                           SEED + 1).x)
    assert a.x.shape == (512, 768) and a.x.dtype == np.float32
    np.testing.assert_allclose(np.linalg.norm(a.x, axis=1), 1.0, rtol=1e-5)
    per_point = np.bincount(a.points, minlength=512)
    assert per_point.mean() == pytest.approx(cfg["labels_per_point"],
                                             rel=0.02)
    assert a.labels.max() < cfg["n_labels"]
    assert len(np.unique(a.points * cfg["n_labels"] + a.labels)) == \
        len(a.labels)


def test_queries_lie_near_their_labels_centroids():
    """A query's embedding carries its labels: two queries that share a
    label are closer than two that share none."""
    cfg = small()
    q = gen_dense.dense_queries(cfg, 256, SEED)
    sets = [set(q.labels[q.points == i]) for i in range(256)]
    sim = q.x @ q.x.T
    share = [(i, j) for i in range(256) for j in range(i + 1, 256)
             if sets[i] & sets[j]]
    apart = [(i, j) for i in range(256) for j in range(i + 1, 256)
             if not sets[i] & sets[j]]
    assert share and apart
    assert np.mean([sim[p] for p in share]) > \
        np.mean([sim[p] for p in apart]) + 0.05


@pytest.mark.parametrize("n", [5, 300])
def test_streamed_reference_equals_the_full_one(n):
    """At a small L the streamed pass reads what the full reference's
    (n, L) arrays give: the k best, the k-th score and its scale, and the
    score and scale at each served label, bit for bit; and judging served
    answers gives the same numbers."""
    cfg, k = small(), 5
    x = gen_dense.make_request_pool(cfg, n, SEED)
    full = reference.serve_reference(cfg, SEED, x)
    ids, top = reference.reference_topk(full.scores, k)
    rng = np.random.default_rng(n)
    served = np.stack([rng.choice(cfg["n_labels"], k, replace=False)
                       for _ in range(n)])
    served[:, 0] = ids[:, 2]          # one served label in the top k
    r = reference_dense.readings(cfg, SEED, x, served, k)
    np.testing.assert_array_equal(r.top_ids, ids)
    np.testing.assert_array_equal(r.top_scores, top)
    np.testing.assert_array_equal(
        r.kth_scale, np.take_along_axis(full.scale, ids[:, k - 1:], 1)[:, 0])
    np.testing.assert_array_equal(
        r.served_scores, np.take_along_axis(full.scores, served, 1))
    np.testing.assert_array_equal(
        r.served_scales, np.take_along_axis(full.scale, served, 1))
    noisy = np.take_along_axis(full.scores, served, 1) * (1 + 1e-3)
    served[1, 1] = served[1, 0]       # a repeated id: a bad answer
    assert reference_dense.judge_answers(
        served, noisy, reference_dense.serve_reference(cfg, SEED, x), k) == \
        reference.judge_answers(served, noisy, full, k)


def run(seconds):
    cfg = small()
    tf = dict(load("traffic", cell()["traffic"] + ".json"), rate_rps=40,
              buckets=[1, 2, 4, 8], check_requests=24, warm_seconds=0.1,
              pool_rows=64)
    import jax
    return harness.run_cell(CELL, SEED, seconds, False,
                            devices=jax.devices(), t_start=time.monotonic(),
                            cfg=cfg, traffic=tf, peak=PEAK)


def test_sound_run_reads_correct():
    res = run(0.5)
    assert res["correct"] is True
    assert set(res["metrics"]) == {"setup_s", "serve_p50_ms"}


@pytest.mark.parametrize("fault", ["control_int8", "half_batch_left_out"])
def test_control_and_fault_read_incorrect(fault, monkeypatch):
    faults.FAULTS[fault](monkeypatch.setattr)
    res = run(0.5)
    assert res["correct"] is False
    if fault in faults.CONTROLS:
        assert res["checks"]["score_err_mean"]["value"] > \
            res["checks"]["score_err_mean"]["limit"]
        assert res["checks"]["bad_answers"]["value"] == 0


def _layer(name):
    return harness.load_module(os.path.join(HERE, "layers", name + ".py"),
                               "layer_" + name.replace(".", "_"))


def test_topk_share_is_the_steps_time_outside_the_kernel():
    """Two step programs of 10 ms; in each the kernel runs first and the
    masks and top-k after it (2.5 and 3 ms). An op outside any step does
    not count, nor a step without a kernel event in the trace."""
    from tracing import Event, Trace

    ms = 1e6
    kernel = ("%_bsr_topk.1 = f32[64,1305344] custom-call(...), "
              'custom_call_target="tpu_custom_call"')

    def ev(name, start, dur, line):
        return Event(name, start * ms, dur * ms, 0, line)

    trace = Trace(events=[
        ev("jit__bsr_topk(7)", 0, 10, "XLA Modules"),
        ev(kernel, 0, 7, "XLA Ops"),
        ev("%fusion = (f32[64,5], s32[64,5]) fusion(...)", 7, 2.5, "XLA Ops"),
        ev("jit__bsr_topk(7)", 20, 10, "XLA Modules"),
        ev(kernel, 20, 6, "XLA Ops"),
        ev("%fusion = (f32[64,5], s32[64,5]) fusion(...)", 26, 3, "XLA Ops"),
        ev("%fusion.2 = f32[8] fusion(...)", 40, 5, "XLA Ops"),
        Event("bench.window", 0.0, 50 * ms, None, "host:python"),
    ], window=(0.0, 50 * ms))
    read = _layer("serve.topk_share").read
    assert read({"trace": trace}) == pytest.approx(27.5)
    trace.events = [e for e in trace.events if e.name != kernel]
    assert read({"trace": trace}) is None
    assert read({}) is None


def test_weight_passes_per_launch_reads_the_servers_counter():
    read = _layer("serve.weight_passes_per_launch").read
    assert read({"server_stats": {"weight_passes": 12, "batches": 8}}) == 1.5
    assert read({"server_stats": {"batches": 8}}) is None
    assert read({"server_stats": {"weight_passes": 0, "batches": 0}}) is None
