"""Training substrate: loss decreases, grad-accum equivalence, optimizer
math, gradient compression, data pipeline determinism."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import RunConfig, get_config
from repro.data.pipeline import SyntheticLMData
from repro.dist.compression import compress_grads, init_error_buffers
from repro.models.layers import Ctx
from repro.optim.adamw import AdamWConfig, adamw_init, adamw_update, cosine_schedule
from repro.train.steps import init_train_state, loss_fn, make_train_step


def test_loss_decreases():
    cfg = get_config("qwen3-0.6b").reduced()
    run = RunConfig(learning_rate=2e-3, warmup_steps=5, total_steps=200)
    state = init_train_state(cfg, jax.random.key(0), run)
    data = SyntheticLMData(cfg.vocab_size, 64, 8, seed=0)
    step = jax.jit(make_train_step(cfg, Ctx(dtype=jnp.float32), run))
    losses = []
    for i in range(40):
        state, m = step(state, data.batch_at(i))
        losses.append(float(m["loss"]))
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.3, losses[:3] + losses[-3:]


def test_grad_accum_equivalence():
    """mb=1 and mb=4 produce (nearly) identical parameter updates."""
    cfg = get_config("qwen3-0.6b").reduced()
    data = SyntheticLMData(cfg.vocab_size, 32, 8, seed=1)
    batch = data.batch_at(0)
    outs = {}
    for mb in (1, 4):
        run = RunConfig(num_microbatches=mb, learning_rate=1e-3,
                        warmup_steps=1, total_steps=10)
        state = init_train_state(cfg, jax.random.key(0), run)
        step = jax.jit(make_train_step(cfg, Ctx(dtype=jnp.float32), run))
        new_state, m = step(state, batch)
        outs[mb] = (new_state, float(m["loss"]))
    p1 = jax.tree.leaves(outs[1][0]["params"])
    p4 = jax.tree.leaves(outs[4][0]["params"])
    for a, b in zip(p1, p4):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, rtol=1e-4)
    assert abs(outs[1][1] - outs[4][1]) < 1e-3


def test_remat_grad_equivalence():
    """Activation checkpointing must not change gradients."""
    cfg = get_config("gemma2-9b").reduced()
    data = SyntheticLMData(cfg.vocab_size, 32, 4, seed=2)
    batch = data.batch_at(0)
    ctx = Ctx(dtype=jnp.float32)
    state = init_train_state(cfg, jax.random.key(0))
    grads = {}
    for policy in ("none", "full"):
        g = jax.grad(lambda p: loss_fn(cfg, p, batch, ctx, policy)[0])(
            state["params"])
        grads[policy] = g
    for a, b in zip(jax.tree.leaves(grads["none"]), jax.tree.leaves(grads["full"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, rtol=1e-4)


def test_adamw_against_manual():
    cfg = AdamWConfig(learning_rate=0.1, b1=0.9, b2=0.99, weight_decay=0.0,
                      warmup_steps=0, total_steps=100, min_lr_frac=1.0,
                      grad_clip_norm=1e9)
    p = {"w": jnp.asarray([1.0, -2.0])}
    g = {"w": jnp.asarray([0.5, 0.5])}
    st_ = adamw_init(p)
    new_p, st2, _ = adamw_update(cfg, g, p, st_)
    m = 0.1 * 0.5
    v = 0.01 * 0.25
    mh, vh = m / 0.1, v / 0.01
    expect = 1.0 - 0.1 * mh / (np.sqrt(vh) + 1e-8)
    np.testing.assert_allclose(float(new_p["w"][0]), expect, rtol=1e-5)


def test_cosine_schedule_shape():
    cfg = AdamWConfig(learning_rate=1.0, warmup_steps=10, total_steps=110,
                      min_lr_frac=0.1)
    assert float(cosine_schedule(cfg, jnp.int32(5))) == pytest.approx(0.5)
    assert float(cosine_schedule(cfg, jnp.int32(10))) == pytest.approx(1.0)
    assert float(cosine_schedule(cfg, jnp.int32(110))) == pytest.approx(0.1)


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_compression_error_feedback(seed):
    """int8 compression with error feedback: per-step quantized values plus
    the carried error reconstruct the running gradient sum exactly."""
    rng = np.random.default_rng(seed)
    g = {"w": jnp.asarray(rng.normal(size=(32,)) * rng.uniform(0.01, 10),
                          jnp.float32)}
    err = init_error_buffers(g)
    total_sent = np.zeros(32)
    n = 4
    for _ in range(n):
        deq, err = compress_grads(g, err)
        total_sent += np.asarray(deq["w"])
    # cumulative(sent) + residual == cumulative(true)
    np.testing.assert_allclose(
        total_sent + np.asarray(err["w"]), n * np.asarray(g["w"]),
        rtol=1e-4, atol=1e-5)


def test_sharded_allreduce_int8_single_device():
    """On a 1-device mesh the int8 all-reduce degenerates to the pack/
    unpack round-trip: pmax of one local scale is that scale."""
    from repro.dist.compression import (
        CompressionConfig, pack_int8, sharded_allreduce_int8, unpack_int8)
    from repro.dist.mesh import make_device_mesh

    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(1, 7, 11)), jnp.float32)
    cfg = CompressionConfig(chunk_size=16)
    # pin a single explicit device: data=1 over several devices is a
    # layout mismatch, which make_device_mesh refuses
    mesh = make_device_mesh(data=1, devices=jax.devices()[:1])
    out = sharded_allreduce_int8(x, mesh, axis="data", cfg=cfg)
    payload, scales = pack_int8(x[0], cfg)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(unpack_int8(payload, scales, (7, 11))),
        rtol=0, atol=0)


def test_sharded_allreduce_int8_multidevice():
    """4 fake host devices: the packed-wire psum (shared pmax scale, int32
    payload sum, one dequant) matches the dense fp32 psum within the
    documented ndev·scale/2 per-element bound — even with per-device
    magnitudes 100× apart, where unreconciled scales would be garbage."""
    import os
    import subprocess
    import sys
    import textwrap

    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        os.environ["JAX_PLATFORMS"] = "cpu"
        import numpy as np
        import jax
        import jax.numpy as jnp
        from repro.dist.compression import (CompressionConfig,
                                            allreduce_int8_bound,
                                            sharded_allreduce_int8)
        from repro.dist.mesh import make_device_mesh

        ndev = jax.device_count()
        assert ndev == 4, ndev
        rng = np.random.default_rng(11)
        # magnitudes 100x apart across devices: scale reconciliation is
        # load-bearing, not decorative
        mags = np.array([0.03, 0.5, 1.0, 3.0])[:, None, None]
        x = (rng.normal(size=(ndev, 13, 9)) * mags).astype(np.float32)
        cfg = CompressionConfig(chunk_size=16)
        mesh = make_device_mesh(data=ndev)
        out = np.asarray(sharded_allreduce_int8(
            jnp.asarray(x), mesh, axis="data", cfg=cfg))
        exact = x.sum(axis=0)

        # per-element bound from the shared chunk scales
        bound = allreduce_int8_bound(x, cfg)
        assert bound.shape == exact.shape
        err = np.abs(out - exact)
        assert (err <= bound + 1e-6).all(), (err.max(), bound.min())
        # and the bound is doing work: int8 is lossy but close
        assert err.max() > 0
        assert np.abs(out - exact).max() / np.abs(exact).max() < 0.05
        print("OK")
    """)
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    res = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=300)
    assert res.returncode == 0 and "OK" in res.stdout, res.stderr[-2000:]


def test_compression_train_still_converges():
    cfg = get_config("qwen3-0.6b").reduced()
    run = RunConfig(learning_rate=2e-3, warmup_steps=5, total_steps=200)
    state = init_train_state(cfg, jax.random.key(0), run, grad_compression=True)
    data = SyntheticLMData(cfg.vocab_size, 64, 8, seed=0)
    step = jax.jit(make_train_step(cfg, Ctx(dtype=jnp.float32), run,
                                   grad_compression=True))
    losses = []
    for i in range(30):
        state, m = step(state, data.batch_at(i))
        losses.append(float(m["loss"]))
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.2


def test_data_deterministic_and_resumable():
    d = SyntheticLMData(1000, 64, 4, seed=9)
    b1, b2 = d.batch_at(17), d.batch_at(17)
    assert (b1["tokens"] == b2["tokens"]).all()
    # labels are next-token shifted
    assert (b1["labels"][:, :-1] == b1["tokens"][:, 1:]).all()
    # different steps differ
    assert not (d.batch_at(18)["tokens"] == b1["tokens"]).all()


def test_data_learnable_structure():
    """Markov structure: next token is the affine map most of the time."""
    d = SyntheticLMData(1000, 256, 2, seed=3, noise=0.1)
    b = d.batch_at(0)
    t, l = np.asarray(b["tokens"]), np.asarray(b["labels"])
    frac = np.mean((31 * t + 17) % 1000 == l)
    assert frac > 0.8
