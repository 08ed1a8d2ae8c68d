"""Checkpoint tests (analogue of reference tests/unit/checkpoint/: zero
checkpoints, tag handling, and universal-checkpoint resume at different
parallelism — test_universal_checkpoint.py)."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from tests.unit.simple_model import batch_of, make_mlp_params, mlp_loss_fn, random_dataset

LR = 1e-2


def _make_engine(stage, params=None, mesh=None):
    config = {
        "train_micro_batch_size_per_gpu": 1,
        "optimizer": {"type": "AdamW", "params": {"lr": LR}},
        "zero_optimization": {"stage": stage, "param_persistence_threshold": 0},
        "steps_per_print": 1000,
    }
    if mesh:
        config["mesh"] = mesh
    params = params if params is not None else make_mlp_params(jax.random.key(0))
    engine, _, _, _ = deepspeed_tpu.initialize(model=mlp_loss_fn, model_parameters=params, config=config)
    return engine


def _params_equal(a, b):
    for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=0, atol=0)


@pytest.mark.parametrize("stage", [1, 3])
def test_save_load_roundtrip(stage, devices8, tmp_path):
    dataset = random_dataset(n=256)
    engine = _make_engine(stage)
    for i in range(3):
        engine.train_batch(batch=batch_of(dataset, i * 8, 8))
    engine.save_checkpoint(str(tmp_path), tag="tag3")

    engine2 = _make_engine(stage, params=make_mlp_params(jax.random.key(42)))
    path, client_state = engine2.load_checkpoint(str(tmp_path), tag="tag3")
    assert path is not None
    _params_equal(engine.params, engine2.params)
    _params_equal(engine.opt_state.master, engine2.opt_state.master)
    assert engine2.global_steps == 3

    # resumed trajectory must continue identically
    b = batch_of(dataset, 64, 8)
    l1 = float(engine.train_batch(batch=b))
    l2 = float(engine2.train_batch(batch=b))
    assert l1 == pytest.approx(l2, rel=1e-6)


def test_latest_tag_autoresume(devices8, tmp_path):
    dataset = random_dataset(n=256)
    engine = _make_engine(1)
    engine.train_batch(batch=batch_of(dataset, 0, 8))
    engine.save_checkpoint(str(tmp_path))  # default tag global_step1
    engine2 = _make_engine(1, params=make_mlp_params(jax.random.key(7)))
    path, _ = engine2.load_checkpoint(str(tmp_path))
    assert path.endswith("global_step1")
    _params_equal(engine.params, engine2.params)


def test_universal_reshape_across_stages(devices8, tmp_path):
    """The UCP property (reference checkpoint/ds_to_universal.py): save under
    ZeRO-3 (sharded params), resume under ZeRO-1 (replicated params) — orbax
    resharding makes every checkpoint universal with no offline conversion."""
    dataset = random_dataset(n=256)
    e3 = _make_engine(3)
    for i in range(2):
        e3.train_batch(batch=batch_of(dataset, i * 8, 8))
    e3.save_checkpoint(str(tmp_path), tag="u")

    e1 = _make_engine(1, params=make_mlp_params(jax.random.key(9)))
    e1.load_checkpoint(str(tmp_path), tag="u")
    _params_equal(e3.params, e1.params)
    # and back: stage-1 save → stage-3 load
    e1.save_checkpoint(str(tmp_path), tag="u2")
    e3b = _make_engine(3, params=make_mlp_params(jax.random.key(11)))
    e3b.load_checkpoint(str(tmp_path), tag="u2")
    _params_equal(e1.params, e3b.params)
    assert not e3b.params["layer_0"]["w"].sharding.is_fully_replicated


def test_universal_reshape_across_mesh(devices8, tmp_path):
    """Resume with a different mesh shape (dp=8 → dp=4×model=2)."""
    dataset = random_dataset(n=256)
    e_a = _make_engine(2, mesh={"data": 8})
    e_a.train_batch(batch=batch_of(dataset, 0, 8))
    e_a.save_checkpoint(str(tmp_path), tag="m")

    e_b = _make_engine(2, params=make_mlp_params(jax.random.key(5)), mesh={"data": 4, "model": 2})
    e_b.load_checkpoint(str(tmp_path), tag="m")
    _params_equal(e_a.params, e_b.params)


def test_missing_checkpoint_returns_none(devices8, tmp_path):
    engine = _make_engine(1)
    path, state = engine.load_checkpoint(str(tmp_path)) or (None, {})
    assert path is None


def test_save_16bit_model(devices8, tmp_path):
    engine = _make_engine(3)
    out = engine.save_16bit_model(str(tmp_path))
    data = np.load(out)
    assert any("layer_0" in k for k in data.files)


def test_zero_to_fp32_offline_converter(devices8, tmp_path):
    """The standalone recovery script (reference utils/zero_to_fp32.py,
    shipped into every checkpoint dir) must rebuild exact fp32 masters in a
    fresh single-device process."""
    import subprocess
    import sys

    params = make_mlp_params(jax.random.key(0))
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=mlp_loss_fn,
        model_parameters=params,
        config={
            "train_micro_batch_size_per_gpu": 1,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-2}},
            "zero_optimization": {"stage": 2},
            "bf16": {"enabled": True},
            "steps_per_print": 1000,
        },
    )
    dataset = random_dataset(n=64)
    engine.train_batch(batch=batch_of(dataset, 0, 8))
    engine.save_checkpoint(str(tmp_path), tag="zf")
    script = tmp_path / "zero_to_fp32.py"
    assert script.exists(), "recovery script must ship with the checkpoint"
    out = tmp_path / "fp32"
    r = subprocess.run(
        [sys.executable, str(script), str(tmp_path), str(out)],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert r.returncode == 0, r.stderr[-800:]
    sd = np.load(str(out) + ".npz")
    master = np.asarray(jax.device_get(engine.opt_state.master["layer_0"]["w"]))
    np.testing.assert_array_equal(sd["layer_0.w"], master)
    assert sd["layer_0.w"].dtype == np.float32
