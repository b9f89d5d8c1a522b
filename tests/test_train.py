"""Optimizer, checkpointing, fault tolerance, grad compression (host side)."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.checkpointer import Checkpointer
from jax import make_mesh, shard_map
from jax.sharding import AxisType
from repro.train.loop import TrainLoop, WatchdogStats
from repro.train.optimizer import OptConfig, opt_init, opt_update, schedule


def quad_problem():
    params = {"w": jnp.asarray([3.0, -2.0]), "b": jnp.asarray(1.5)}

    def loss(p):
        return jnp.sum(p["w"] ** 2) + p["b"] ** 2

    return params, loss


@pytest.mark.parametrize("kind", ["adamw", "adafactor"])
def test_optimizer_converges(kind):
    cfg = OptConfig(kind=kind, lr=0.1, weight_decay=0.0, warmup_steps=0, total_steps=10000)
    params, loss = quad_problem()
    state = opt_init(cfg, params)
    for _ in range(300):
        grads = jax.grad(loss)(params)
        params, state, _ = opt_update(cfg, params, grads, state)
    assert float(loss(params)) < 1e-2, kind


def test_schedule_warmup_cosine():
    cfg = OptConfig(lr=1.0, warmup_steps=10, total_steps=100, min_lr_frac=0.1)
    assert float(schedule(cfg, jnp.asarray(0))) == 0.0
    assert abs(float(schedule(cfg, jnp.asarray(10))) - 1.0) < 1e-6
    assert float(schedule(cfg, jnp.asarray(100))) <= 0.11


def test_grad_clipping():
    from repro.train.optimizer import clip_by_global_norm, global_norm

    tree = {"a": jnp.full((10,), 100.0)}
    clipped, n = clip_by_global_norm(tree, 1.0)
    assert abs(float(global_norm(clipped)) - 1.0) < 1e-5
    assert float(n) > 100


# ----------------------------------------------------------------------
def test_checkpoint_roundtrip(tmp_path):
    ck = Checkpointer(str(tmp_path), keep=2, async_save=False)
    state = {"params": {"w": jnp.arange(6.0).reshape(2, 3)}, "step": jnp.asarray(7)}
    ck.save(7, state)
    restored, step = ck.restore(state)
    assert step == 7
    assert np.array_equal(np.asarray(restored["params"]["w"]), np.arange(6.0).reshape(2, 3))


def test_checkpoint_retention_and_latest(tmp_path):
    ck = Checkpointer(str(tmp_path), keep=2, async_save=False)
    state = {"x": jnp.asarray(1.0)}
    for s in (10, 20, 30):
        ck.save(s, state)
    assert ck.all_steps() == [20, 30]
    assert ck.latest_step() == 30


def test_corrupt_checkpoint_skipped(tmp_path):
    """Node-failure path: newest snapshot corrupted -> fall back."""
    ck = Checkpointer(str(tmp_path), keep=5, async_save=False)
    state = {"x": jnp.asarray(1.0)}
    ck.save(1, state)
    ck.save(2, {"x": jnp.asarray(2.0)})
    # corrupt step 2
    d = os.path.join(str(tmp_path), "step_0000000002")
    fname = [f for f in os.listdir(d) if f.endswith(".npy")][0]
    with open(os.path.join(d, fname), "wb") as f:
        f.write(b"garbage")
    restored, step = ck.restore_latest_valid(state)
    assert step == 1
    assert float(restored["x"]) == 1.0


def test_async_save_surfaces_errors(tmp_path):
    ck = Checkpointer(str(tmp_path / "sub"), keep=1, async_save=True)
    ck.save(1, {"x": jnp.asarray(1.0)})
    ck.wait()
    assert ck.latest_step() == 1


def test_elastic_reshard_identity():
    """Checkpoint -> reshard to a different (host) mesh keeps values."""
    from repro.checkpoint.checkpointer import reshard
    from repro.launch.mesh import make_local_mesh
    from jax.sharding import PartitionSpec as P

    mesh = make_local_mesh(1, 1)
    state = {"w": jnp.arange(8.0).reshape(2, 4)}
    specs = {"w": P(None, None)}
    out = reshard(state, mesh, specs)
    assert np.array_equal(np.asarray(out["w"]), np.asarray(state["w"]))


# ----------------------------------------------------------------------
def test_watchdog_flags_stragglers():
    w = WatchdogStats()
    for s in range(10):
        assert not w.update(s, 0.1)
    assert w.update(10, 1.0)  # 10x the EWMA
    assert w.stragglers == [10]


def test_train_loop_resume(tmp_path):
    cfg = OptConfig(lr=0.05, warmup_steps=0, total_steps=1000, weight_decay=0.0)
    params, loss = quad_problem()

    def step(state, batch):
        grads = jax.grad(loss)(state["params"])
        p, o, extra = opt_update(cfg, state["params"], grads, state["opt"])
        return {"params": p, "opt": o, "step": state["step"] + 1}, {
            "loss": loss(state["params"]), **extra}

    def data():
        while True:
            yield {}

    ck = Checkpointer(str(tmp_path), keep=3, async_save=False)
    state0 = {"params": params, "opt": opt_init(cfg, params), "step": jnp.asarray(0)}
    loop = TrainLoop(train_step=jax.jit(step), data_iter=data(), checkpointer=ck, ckpt_every=5)
    state, logs = loop.run(state0, 12)
    assert ck.latest_step() == 10
    # resume and continue
    restored, start = TrainLoop.resume_or_init(ck, state0)
    assert start == 10
    state2, logs2 = loop.run(restored, 5, start_step=start)
    assert logs2[-1]["loss"] < logs[0]["loss"]


# ----------------------------------------------------------------------
def test_grad_compression_shapes():
    """Quantized psum approximates the true sum (single-device axis)."""
    from functools import partial

    from repro.train.grad_compression import psum_int8, psum_topk

    mesh = make_mesh((1,), ("d",), axis_types=(AxisType.Auto,))
    x = jnp.asarray(np.random.default_rng(0).normal(size=(37, 5)), jnp.float32)

    @partial(shard_map, mesh=mesh,
             in_specs=jax.sharding.PartitionSpec(), out_specs=jax.sharding.PartitionSpec())
    def f(x):
        return psum_int8(x, "d")

    got = f(x)
    assert float(jnp.max(jnp.abs(got - x))) < 2e-2  # quantization error only

    @partial(shard_map, mesh=mesh,
             in_specs=jax.sharding.PartitionSpec(), out_specs=(jax.sharding.PartitionSpec(),) * 2)
    def g(x):
        return psum_topk(x, "d", k_frac=1.0)

    total, resid = g(x)
    assert float(jnp.max(jnp.abs(total - x))) < 1e-6  # k=100%: lossless
