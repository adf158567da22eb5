"""Multi-device sharding semantics, run in subprocesses with
XLA_FLAGS=--xla_force_host_platform_device_count=8 (the main test process
keeps the 1 real device, per the brief)."""

import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env, timeout=560)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


def test_train_sharded_equals_single_device():
    """Paper-faithful label sharding AND beyond-paper data sharding must both
    reproduce the single-device Algorithm 1 solution."""
    out = _run("""
        import jax, jax.numpy as jnp
        from repro.compat import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        from repro.data.xmc import make_xmc_dataset
        from repro.core.dismec import DiSMECConfig, train, train_sharded
        d = make_xmc_dataset(n_train=256, n_test=50, n_features=512,
                             n_labels=48, seed=0)
        X, Y = jnp.asarray(d.X_train), jnp.asarray(d.Y_train)
        cfg = DiSMECConfig(label_batch=48)
        m1 = train(X, Y, cfg)
        m2 = train_sharded(X, Y, cfg, mesh)
        m3 = train_sharded(X, Y, cfg, mesh, shard_data=True)
        assert jnp.allclose(m1.W, m2.W, atol=1e-3), "label-sharded mismatch"
        assert jnp.allclose(m1.W, m3.W, atol=1e-3), "data-sharded mismatch"
        print("OK")
    """)
    assert "OK" in out


def test_label_padding_under_sharding():
    """L=50 not divisible by 8 shards: result must still be exact for the
    real labels (padding sliced away)."""
    out = _run("""
        import jax, jax.numpy as jnp
        from repro.compat import make_mesh
        mesh = make_mesh((1, 8), ("data", "model"))
        from repro.data.xmc import make_xmc_dataset
        from repro.core.dismec import DiSMECConfig, train, train_sharded
        d = make_xmc_dataset(n_train=200, n_test=50, n_features=512,
                             n_labels=50, seed=1)
        X, Y = jnp.asarray(d.X_train), jnp.asarray(d.Y_train)
        cfg = DiSMECConfig(label_batch=50)
        m1 = train(X, Y, cfg)
        m2 = train_sharded(X, Y, cfg, mesh)
        assert m2.W.shape == m1.W.shape == (50, 512)
        assert jnp.allclose(m1.W, m2.W, atol=1e-3)
        print("OK")
    """)
    assert "OK" in out


def test_data_sharded_non_divisible_n():
    """N not divisible by the data axis: the psum path pads instances with
    zero rows + all-negative signs (gradient/Hessian contributions vanish,
    the constant objective offset is subtracted) and must reproduce the
    unsharded solution exactly — the old code hard-asserted divisibility."""
    out = _run("""
        import jax, jax.numpy as jnp
        from repro.compat import make_mesh
        mesh = make_mesh((4, 2), ("data", "model"))
        from repro.data.xmc import make_xmc_dataset
        from repro.core.dismec import DiSMECConfig, train, train_sharded
        d = make_xmc_dataset(n_train=201, n_test=50, n_features=512,
                             n_labels=48, seed=3)   # 201 % 4 == 1
        X, Y = jnp.asarray(d.X_train), jnp.asarray(d.Y_train)
        cfg = DiSMECConfig(label_batch=48)
        m1 = train(X, Y, cfg)
        m2 = train_sharded(X, Y, cfg, mesh, shard_data=True)
        assert m2.W.shape == m1.W.shape == (48, 512)
        assert jnp.allclose(m1.W, m2.W, atol=1e-3), "padded psum mismatch"
        print("OK")
    """)
    assert "OK" in out


def test_streaming_pipeline_on_mesh_matches_train():
    """The full composition: label-batch scheduler (layer 1) over the
    mesh-sharded solver (layer 2) with frequency-balanced shard dealing,
    streamed to a multi-shard checkpoint — must land on the single-device
    Algorithm 1 solution."""
    out = _run("""
        import tempfile
        import numpy as np
        import jax, jax.numpy as jnp
        from repro.compat import make_mesh
        mesh = make_mesh((1, 4), ("data", "model"))
        from repro.checkpoint.io import load_block_sparse
        from repro.core.dismec import DiSMECConfig, train
        from repro.data.xmc import make_xmc_dataset
        from repro.train.xmc import XMCTrainJob
        d = make_xmc_dataset(n_train=200, n_test=50, n_features=1024,
                             n_labels=96, seed=4)
        X, Y = jnp.asarray(d.X_train), jnp.asarray(d.Y_train)
        cfg = DiSMECConfig(label_batch=32)
        job = XMCTrainJob(cfg=cfg, mesh=mesh, balance=True,
                          block_shape=(16, 16))
        with tempfile.TemporaryDirectory() as out_dir:
            res = job.run(X, Y, out_dir)
            assert res.complete and res.n_batches == 3
            bsr, meta = load_block_sparse(out_dir)
            W = np.asarray(bsr.to_dense())[:96, :1024]
        m1 = train(X, Y, cfg)
        assert np.allclose(W, np.asarray(m1.W), atol=1e-3)
        print("OK")
    """)
    assert "OK" in out


def test_distributed_topk_merge():
    """Shard-local top-k + global merge == dense top-k (paper §2.2.1)."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.compat import make_mesh
        mesh = make_mesh((1, 8), ("data", "model"))
        from repro.core.prediction import predict_topk, predict_topk_sharded
        rng = np.random.default_rng(0)
        W = jnp.asarray(rng.normal(size=(64, 128)), jnp.float32)
        X = jnp.asarray(rng.normal(size=(16, 128)), jnp.float32)
        s1, i1 = predict_topk(X, W, 5)
        s2, i2 = predict_topk_sharded(X, W, 5, mesh)
        assert jnp.allclose(s1, s2, atol=1e-5)
        assert (np.asarray(i1) == np.asarray(i2)).all()
        print("OK")
    """)
    assert "OK" in out


def test_dismec_head_label_sharded_loss_invariance():
    """The DiSMEC OvR head loss must be identical whether the head weight is
    replicated or label-sharded over `model` — the technique's key property
    (no logits collective needed, only scalar psum)."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core.head import ovr_squared_hinge_loss
        from repro.compat import make_mesh
        mesh = make_mesh((1, 8), ("data", "model"))
        rng = np.random.default_rng(0)
        V, d, T = 64, 32, 24
        W = jnp.asarray(rng.normal(size=(V, d)) * 0.1, jnp.float32)
        feats = jnp.asarray(rng.normal(size=(T, d)), jnp.float32)
        tgt = jnp.asarray(rng.integers(0, V, (T,)), jnp.int32)
        base = ovr_squared_hinge_loss(W, feats, tgt)
        Wsh = jax.device_put(W, NamedSharding(mesh, P("model", None)))
        with mesh:
            sh = jax.jit(lambda w: ovr_squared_hinge_loss(w, feats, tgt))(Wsh)
        assert jnp.allclose(base, sh, rtol=1e-5), (base, sh)
        print("OK")
    """)
    assert "OK" in out


def test_balanced_sharding_solution_invariance():
    """Frequency-balanced label sharding (beyond paper) permutes labels
    across shards but must return the IDENTICAL model."""
    out = _run("""
        import jax, jax.numpy as jnp
        from repro.compat import make_mesh
        mesh = make_mesh((1, 8), ("data", "model"))
        from repro.data.xmc import make_xmc_dataset
        from repro.core.dismec import DiSMECConfig, train_sharded
        d = make_xmc_dataset(n_train=200, n_test=50, n_features=512,
                             n_labels=64, beta=1.2, seed=2)
        X, Y = jnp.asarray(d.X_train), jnp.asarray(d.Y_train)
        cfg = DiSMECConfig(label_batch=64)
        m_plain = train_sharded(X, Y, cfg, mesh)
        m_bal = train_sharded(X, Y, cfg, mesh, balance=True)
        assert jnp.allclose(m_plain.W, m_bal.W, atol=1e-3)
        print("OK")
    """)
    assert "OK" in out


def test_dryrun_smoke_config_compiles_on_8dev_mesh():
    """A miniature of deliverable (e): lower+compile a smoke config train
    step on a (2, 4) mesh via the dryrun machinery."""
    out = _run("""
        import jax
        from repro.launch.dryrun import build_lowerable
        from repro.compat import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        fn, args = build_lowerable("qwen1.5-0.5b", "train_4k", mesh,
                                   smoke=True)
        with mesh:
            compiled = jax.jit(fn).lower(*args).compile()
        assert compiled.cost_analysis()["flops"] > 0
        print("OK")
    """)
    assert "OK" in out
