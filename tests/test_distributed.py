"""Distributed substrate (process-local parts): sharding-rule resolution
(AbstractMesh), checkpointing, elastic policy, compression, fault
tolerance.  Tests needing real multi-device meshes live in
test_mesh_subprocess.py (separate process so device count doesn't leak)."""

import pathlib
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh

from repro.checkpoint import checkpointer as ck
from repro.configs import get_config
from repro.distributed import elastic, fault_tolerance as ft
from repro.distributed import param_specs, pipeline as pp, sharding
from repro.models import lm
from repro.train import compression as comp
from repro.train import train_step as ts


def _amesh(shape, names):
    # Device-free mesh: resolves sharding rules without any real devices.
    return AbstractMesh(tuple(shape), tuple(names))


# -- sharding rules (AbstractMesh: no devices needed) ----------------------------

def test_param_specs_divisibility():
    cfg = get_config("phi35_moe", smoke=True)
    params = jax.eval_shape(lambda: lm.init_lm(jax.random.key(0), cfg))
    mesh = _amesh((2, 4), ("data", "model"))
    shardings = param_specs.param_shardings(params, mesh,
                                            sharding.TRAIN_RULES)
    p_flat = jax.tree_util.tree_flatten_with_path(params)[0]
    s_flat = jax.tree_util.tree_flatten_with_path(shardings)[0]
    n_sharded = 0
    for (path, leaf), (_, s) in zip(p_flat, s_flat):
        spec = tuple(s.spec) + (None,) * (len(leaf.shape) - len(s.spec))
        for dim, part in zip(leaf.shape, spec):
            if part is None:
                continue
            size = int(np.prod([mesh.shape[a] for a in
                                (part if isinstance(part, tuple)
                                 else (part,))]))
            assert dim % size == 0, (path, leaf.shape, s.spec)
            n_sharded += 1
    assert n_sharded > 10, "rules resolved to nothing"


def test_decode_rules_shard_cache_seq():
    cfg = get_config("deepseek_67b", smoke=True)
    caches = jax.eval_shape(lambda: lm.init_cache(cfg, 4, 32))
    mesh = _amesh((2, 4), ("data", "model"))
    sh = param_specs.cache_shardings(caches, mesh, sharding.DECODE_RULES)
    k_shard = sh[0]["k"]
    # (count, B, S, KV, dh): seq dim (idx 2) on 'model'
    assert k_shard.spec[2] == "model", k_shard.spec


def test_constrain_safe_noop_without_mesh():
    x = jnp.ones((4, 4))
    y = sharding.constrain_safe(x, ("batch", None))
    np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_elastic_reshard_plan_reports_changes():
    cfg = get_config("stablelm_3b", smoke=True)
    state = jax.eval_shape(lambda: ts.init_train_state(
        jax.random.key(0), cfg, ts.TrainConfig()))
    a = _amesh((4, 2), ("data", "model"))
    b = _amesh((2, 4), ("data", "model"))
    _, report = elastic.reshard_plan(state, a, b, sharding.TRAIN_RULES)
    assert report.n_leaves > 0
    assert isinstance(report.changed, tuple)


def test_elastic_batch_policy():
    assert elastic.rescale_batch(256, 16, 8) == 256
    with pytest.raises(ValueError):
        elastic.rescale_batch(100, 16, 64)


# -- checkpointing ----------------------------------------------------------------

def test_checkpoint_roundtrip_async_and_gc():
    state = {"w": jnp.arange(6.0), "step": jnp.int32(3)}
    with tempfile.TemporaryDirectory() as d:
        acp = ck.AsyncCheckpointer(d, keep=2)
        for s in (1, 2, 3):
            acp.save(state, s)
        acp.wait()
        assert ck.latest_step(d) == 3
        assert len(list(pathlib.Path(d).glob("step_*"))) == 2
        target = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state)
        got, step = ck.restore(d, target)
        assert step == 3
        np.testing.assert_array_equal(np.asarray(got["w"]),
                                      np.asarray(state["w"]))


def test_checkpoint_atomic_publish():
    """A .tmp dir (crashed save) is never picked up as latest."""
    state = {"w": jnp.ones(3)}
    with tempfile.TemporaryDirectory() as d:
        ck.save(d, state, 1)
        (pathlib.Path(d) / "step_00000002.tmp").mkdir()
        assert ck.latest_step(d) == 1


def test_checkpoint_shape_mismatch_raises():
    with tempfile.TemporaryDirectory() as d:
        ck.save(d, {"w": jnp.ones((3,))}, 1)
        with pytest.raises(ValueError):
            ck.restore(d, {"w": jax.ShapeDtypeStruct((4,), jnp.float32)})


# -- pipeline (host-level helpers) --------------------------------------------------

def test_pipeline_stage_ranges():
    assert pp.pipeline_stages(10, 4) == [(0, 3), (3, 6), (6, 8), (8, 10)]
    assert pp.pipeline_stages(8, 2) == [(0, 4), (4, 8)]


# -- gradient compression -----------------------------------------------------------

def test_error_feedback_converges():
    w_star = jnp.asarray(np.random.default_rng(0).normal(size=(32,)),
                         jnp.float32)

    def grad(w):
        return {"w": w["w"] - w_star}

    runs = {}
    for compressed in (False, True):
        w = {"w": jnp.zeros(32)}
        est = comp.init_state(w)
        for _ in range(60):
            g = grad(w)
            if compressed:
                q, est = comp.compress(g, est)
                g = comp.decompress(q)
            w = jax.tree.map(lambda p, gg: p - 0.2 * gg, w, g)
        runs[compressed] = float(jnp.linalg.norm(w["w"] - w_star))
    assert runs[True] < 1e-2, runs


def test_compression_is_4x():
    g = {"a": jnp.zeros((1024,), jnp.float32)}
    q, _ = comp.compress(g, comp.init_state(g))
    assert q["a"]["q"].dtype == jnp.int8
    assert q["a"]["q"].nbytes * 4 == g["a"].nbytes


# -- fault tolerance -----------------------------------------------------------------

def test_heartbeat_and_straggler():
    t = [0.0]
    reg = ft.HeartbeatRegistry(["w0", "w1"], timeout=10, clock=lambda: t[0])
    assert reg.healthy()
    t[0] = 11.0
    reg.ping("w0")
    assert reg.dead_workers() == ["w1"]

    mon = ft.StragglerMonitor(k=5.0, min_samples=4)
    for i in range(8):
        assert mon.observe("w0", i, 1.0 + 0.01 * i) is None
    rep = mon.observe("w1", 9, 100.0)
    assert rep is not None and rep.worker == "w1"
    mon.observe("w1", 10, 100.0)
    mon.observe("w1", 11, 100.0)
    assert mon.should_replace("w1")


def test_restart_driver_replays_deterministically():
    saved = {}
    crashed = {"done": False}

    def step_fn(s, i):
        if i == 6 and not crashed["done"]:
            crashed["done"] = True
            raise RuntimeError("boom")
        return s + i

    final, stats = ft.run_with_restarts(
        init_fn=lambda: 0, step_fn=step_fn,
        save_fn=lambda s, i: saved.update(ck=(s, i)),
        restore_fn=lambda: saved.get("ck"),
        total_steps=10, checkpoint_every=3)
    assert stats.restarts == 1
    assert final == sum(range(10))
