"""Compressed-collective tests: 1-bit error-feedback allreduce, OnebitAdam's
compressed exchange, and ZeRO++ qgZ/qwZ quantized gradient/weight collectives
(analogue of reference tests/unit/ops compressed-backend + test_zeropp.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import deepspeed_tpu
from deepspeed_tpu.runtime.comm.compressed import (
    compressed_allreduce,
    pack_signs,
    padded_size,
    unpack_signs,
)

from tests.unit.simple_model import batch_of, make_mlp_params, mlp_loss_fn, random_dataset

LR = 1e-2


def _mesh8():
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:8]), ("data",))


def _shardmapped_allreduce(mesh):
    """compressed_allreduce over per-rank rows of [W, ...] inputs."""

    def run(x, we, se):
        avg, we2, se2 = compressed_allreduce(x[0], we[0], se[0], "data")
        return avg, we2[None], se2[None]

    return jax.shard_map(
        run,
        mesh=mesh,
        in_specs=(P("data"), P("data"), P("data")),
        out_specs=(P(None), P("data"), P("data")),
        axis_names={"data"},
        check_vma=False,
    )


# ---------------------------------------------------------------------------
# wire format
# ---------------------------------------------------------------------------
def test_pack_signs_roundtrip_and_bytes():
    x = jax.random.normal(jax.random.key(0), (4, 64))
    packed = pack_signs(x)
    # bytes on the wire: one bit per element
    assert packed.dtype == jnp.uint8
    assert packed.nbytes == x.size // 8
    signs = unpack_signs(packed)
    np.testing.assert_array_equal(np.asarray(signs), np.where(np.asarray(x) >= 0, 1.0, -1.0))


def test_compressed_allreduce_exact_for_uniform_signs(devices8):
    """When every element of a rank's buffer has the same magnitude, sign*scale
    reconstructs it exactly: the two-phase pipeline must return the exact mean."""
    mesh = _mesh8()
    W, n = 8, 128
    n_pad = padded_size(n, W)
    # rank r contributes (-1)^r * (r+1): per-chunk scale == |value| exactly
    x = jnp.stack([jnp.full((n_pad,), (-1.0) ** r * (r + 1), jnp.float32) for r in range(W)])
    we = jnp.zeros((W, n_pad), jnp.float32)
    se = jnp.zeros((W, n_pad // W), jnp.float32)

    fn = jax.jit(_shardmapped_allreduce(mesh))
    avg, new_we, new_se = fn(x, we, se)
    expected = float(np.mean([(-1.0) ** r * (r + 1) for r in range(W)]))
    np.testing.assert_allclose(np.asarray(avg), expected, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(new_we), 0.0, atol=1e-6)


def test_compressed_allreduce_error_feedback_converges(devices8):
    """Error feedback: the *accumulated* transmitted signal tracks the
    accumulated true mean (the 1-bit Adam convergence argument)."""
    mesh = _mesh8()
    W, n = 8, 256
    n_pad = padded_size(n, W)
    rng = np.random.default_rng(0)
    x_np = rng.normal(size=(W, n_pad)).astype(np.float32)
    true_mean = x_np.mean(axis=0)

    fn = jax.jit(_shardmapped_allreduce(mesh))
    we = jnp.zeros((W, n_pad), jnp.float32)
    se = jnp.zeros((W, n_pad // W), jnp.float32)
    x = jnp.asarray(x_np)
    total = np.zeros(n_pad, np.float32)
    steps = 30
    for _ in range(steps):  # same value repeatedly: avg of outputs → true mean
        avg, we, se = fn(x, we, se)
        total += np.asarray(avg)
    err = np.abs(total / steps - true_mean).mean() / (np.abs(true_mean).mean() + 1e-9)
    assert err < 0.15, f"error-feedback mean did not converge: rel err {err:.3f}"


# ---------------------------------------------------------------------------
# OnebitAdam end-to-end
# ---------------------------------------------------------------------------
def _onebit_reference_losses(params, dataset, n_steps, batch):
    """Hand-rolled 1-bit Adam semantics with exact (uncompressed) exchange:
    valid as a trajectory reference for the warmup phase."""
    b1, b2, eps = 0.9, 0.999, 1e-8
    mu = jax.tree.map(lambda p: jnp.zeros_like(p), params)
    nu = jax.tree.map(lambda p: jnp.zeros_like(p), params)
    losses, pos = [], 0
    for _ in range(n_steps):
        b = batch_of(dataset, pos, batch)
        pos += batch
        loss, g = jax.value_and_grad(mlp_loss_fn)(params, b)
        mu = jax.tree.map(lambda m, gg: b1 * m + (1 - b1) * gg, mu, g)
        nu = jax.tree.map(lambda v, gg: b2 * v + (1 - b2) * gg**2, nu, g)
        params = jax.tree.map(lambda p, m, v: p - LR * m / (jnp.sqrt(v) + eps), params, mu, nu)
        losses.append(float(loss))
    return losses


def test_onebit_adam_engine(devices8):
    """Warmup steps match exact Adam (no bias correction); compressed phase
    keeps training (loss decreasing, state finite)."""
    freeze = 3
    n_steps = 10
    dataset = random_dataset(n=8 * 8 * n_steps)
    params = make_mlp_params(jax.random.key(0))
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=mlp_loss_fn,
        model_parameters=params,
        config={
            "train_micro_batch_size_per_gpu": 8,
            "optimizer": {
                "type": "OneBitAdam",
                "params": {"lr": LR, "freeze_step": freeze, "betas": [0.9, 0.999]},
            },
            "zero_optimization": {"stage": 0},
            "mesh": {"data": 8},
            "steps_per_print": 1000,
        },
    )
    assert getattr(engine.optimizer, "collective_grad_exchange", False)
    losses = []
    pos = 0
    for _ in range(n_steps):
        b = batch_of(dataset, pos, 64)
        pos += 64
        losses.append(float(engine.train_batch(batch=b)))
    ref = _onebit_reference_losses(make_mlp_params(jax.random.key(0)), dataset, freeze, 64)
    np.testing.assert_allclose(losses[:freeze], ref, rtol=2e-4)
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0] * 0.8, f"compressed phase not training: {losses}"


def test_onebit_wire_is_packed_bits(devices8):
    """The compiled step's only full-size cross-replica payload is the uint8
    packed-sign all-to-all — assert the collectives operate on u8."""
    params = make_mlp_params(jax.random.key(0))
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=mlp_loss_fn,
        model_parameters=params,
        config={
            "train_micro_batch_size_per_gpu": 8,
            "optimizer": {"type": "OneBitAdam", "params": {"lr": LR, "freeze_step": 1}},
            "zero_optimization": {"stage": 0},
            "mesh": {"data": 8},
            "steps_per_print": 1000,
        },
    )
    dataset = random_dataset(n=64)
    b = batch_of(dataset, 0, 64)
    stacked = engine._stack_batch(b)
    step = engine._build_train_step()
    import jax.numpy as jnp

    shardings = engine._batch_shardings(stacked, leading_gas_dim=True)
    stacked = jax.device_put(stacked, shardings)
    lowered = step.lower(
        engine.params, engine.opt_state, engine.scaler_state, jnp.int32(0), jnp.float32(LR), stacked,
        engine._loco_state,
    )
    hlo = lowered.compile().as_text()
    assert "all-to-all" in hlo
    # the sign payload crosses as u8
    import re

    a2a_types = re.findall(r"(\w+)\[[\d,]*\][^\n]*all-to-all", hlo)
    assert any(t == "u8" for t in a2a_types), f"no u8 all-to-all found: {set(a2a_types)}"


def test_onebit_lamb_single_worker_refused():
    """OnebitLamb now exists (tests/unit/test_zero_one_lamb.py) but still
    refuses a 1-worker world, where compression has no wire to save."""
    params = make_mlp_params(jax.random.key(0))
    from deepspeed_tpu.parallel.topology import Topology, reset_topology

    reset_topology()
    try:
        with pytest.raises(NotImplementedError):
            deepspeed_tpu.initialize(
                model=mlp_loss_fn,
                model_parameters=params,
                mpu=Topology(data=1, devices=jax.devices()[:1]),
                config={
                    "train_micro_batch_size_per_gpu": 8,
                    "optimizer": {"type": "OneBitLamb", "params": {"lr": LR}},
                    "steps_per_print": 1000,
                },
            )
    finally:
        reset_topology()


# ---------------------------------------------------------------------------
# qgZ / qwZ
# ---------------------------------------------------------------------------
def _engine_losses_with(config_extra, stage, n_steps=8):
    dataset = random_dataset(n=64 * n_steps)
    params = make_mlp_params(jax.random.key(0))
    zcfg = {"stage": stage, "param_persistence_threshold": 0}
    zcfg.update(config_extra)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=mlp_loss_fn,
        model_parameters=params,
        config={
            "train_micro_batch_size_per_gpu": 8,
            "optimizer": {"type": "AdamW", "params": {"lr": LR}},
            "zero_optimization": zcfg,
            "mesh": {"data": 8},
            "steps_per_print": 1000,
        },
    )
    losses, pos = [], 0
    for _ in range(n_steps):
        b = batch_of(dataset, pos, 64)
        pos += 64
        losses.append(float(engine.train_batch(batch=b)))
    return losses, engine


@pytest.mark.parametrize("stage", [1, 2, 3])
def test_qgz_trajectory_close_to_exact(stage, devices8):
    """zero_quantized_gradients: int8 block-quantized gradient exchange must
    track the full-precision trajectory within quantization tolerance."""
    exact, _ = _engine_losses_with({}, stage)
    quant, _ = _engine_losses_with({"zero_quantized_gradients": True}, stage)
    assert np.isfinite(quant).all()
    np.testing.assert_allclose(quant, exact, rtol=0.08)
    assert quant[-1] < quant[0]


def test_qgz_wire_is_int8(devices8, monkeypatch):
    """The gradient exchange payload must be int8 on the wire (threshold
    lowered so the tiny test model's leaves qualify as 'bulk')."""
    from deepspeed_tpu.runtime.engine import DeepSpeedEngine

    monkeypatch.setattr(DeepSpeedEngine, "QGZ_MIN_SIZE", 0)
    dataset = random_dataset(n=64)
    params = make_mlp_params(jax.random.key(0))
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=mlp_loss_fn,
        model_parameters=params,
        config={
            "train_micro_batch_size_per_gpu": 8,
            "optimizer": {"type": "AdamW", "params": {"lr": LR}},
            "zero_optimization": {"stage": 2, "zero_quantized_gradients": True},
            "mesh": {"data": 8},
            "steps_per_print": 1000,
        },
    )
    b = batch_of(dataset, 0, 64)
    stacked = engine._stack_batch(b)
    step = engine._build_train_step()
    stacked = jax.device_put(stacked, engine._batch_shardings(stacked, leading_gas_dim=True))
    hlo = step.lower(
        engine.params, engine.opt_state, engine.scaler_state, jnp.int32(0), jnp.float32(LR), stacked,
        engine._loco_state,
    ).compile().as_text()
    import re

    a2a_types = re.findall(r"(\w+)\[[\d,]*\][^\n]*all-to-all", hlo)
    assert any(t == "s8" for t in a2a_types), f"no s8 all-to-all found: {set(a2a_types)}"


def test_qgz_imperative_path(devices8):
    """forward/backward/step must run the same quantized exchange as
    train_batch (no silent full-precision fallback)."""
    dataset = random_dataset(n=64 * 4)
    params = make_mlp_params(jax.random.key(0))
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=mlp_loss_fn,
        model_parameters=params,
        config={
            "train_micro_batch_size_per_gpu": 8,
            "optimizer": {"type": "AdamW", "params": {"lr": LR}},
            "zero_optimization": {"stage": 2, "zero_quantized_gradients": True},
            "mesh": {"data": 8},
            "steps_per_print": 1000,
        },
    )
    fused, _ = _engine_losses_with({"zero_quantized_gradients": True}, 2, n_steps=4)
    losses, pos = [], 0
    for _ in range(4):
        b = batch_of(dataset, pos, 64)
        pos += 64
        loss = engine(b)
        engine.backward(loss)
        engine.step()
        losses.append(float(loss))
    np.testing.assert_allclose(losses, fused, rtol=1e-5)


def test_loco_trajectory_close_to_exact(devices8, monkeypatch):
    """ZeRO++ LoCo (zeropp_loco_param): error-feedback on the qgZ exchange
    must track the full-precision trajectory at least as closely as plain
    qgZ (reference all_to_all_loco_quant_reduce semantics)."""
    from deepspeed_tpu.runtime.engine import DeepSpeedEngine

    monkeypatch.setattr(DeepSpeedEngine, "QGZ_MIN_SIZE", 0)  # tiny test leaves
    exact, _ = _engine_losses_with({}, 2)
    loco, engine = _engine_losses_with(
        {
            "zero_quantized_gradients": True,
            "zeropp_loco_param": {"err_beta": 0.8, "reset_T": 1024},
        },
        2,
    )
    assert np.isfinite(loco).all()
    np.testing.assert_allclose(loco, exact, rtol=0.08)
    assert loco[-1] < loco[0]
    # error buffers became live state: eligible leaves carry [W, ...] bf16
    sizes = [e.size for e in jax.tree_util.tree_leaves(engine._loco_state)]
    assert any(s > 0 for s in sizes), "no live LoCo error buffers"


def test_loco_error_feedback_beats_plain_qgz_int4(devices8, monkeypatch):
    """At int4 wire precision the quantization error is large enough that
    error feedback measurably tightens the trajectory — the property LoCo
    exists for. Compare mean |loss - exact| over the run."""
    from deepspeed_tpu.runtime.engine import DeepSpeedEngine
    from deepspeed_tpu.runtime.zero import overlap

    monkeypatch.setattr(DeepSpeedEngine, "QGZ_MIN_SIZE", 0)
    # int4 on the exchange the engine runs: with overlap_comm (the default)
    # that is the BUCKETED pair, which the engine imports as it builds its
    # step. Patching the per-leaf functions of block_quant steered nothing
    # (the run stayed int8, both errors ~1e-5 of float noise, and the
    # comparison was a coin toss): the counts below fail if that recurs.
    orig_rs = overlap.bucketed_quantized_reduce_scatter
    orig_loco = overlap.bucketed_loco_quantized_reduce_scatter
    traced = {"plain": 0, "loco": 0}

    def int4_rs(leaves, dims, axis_name, bits=8, block_size=256, mean=True):
        traced["plain"] += 1
        return orig_rs(leaves, dims, axis_name, 4, 64, mean)

    def int4_loco(leaves, errs, dims, axis_name, bits=8, block_size=256,
                  err_beta=0.8, mean=True):
        traced["loco"] += 1
        return orig_loco(leaves, errs, dims, axis_name, 4, 64, err_beta, mean)

    monkeypatch.setattr(overlap, "bucketed_quantized_reduce_scatter", int4_rs)
    monkeypatch.setattr(overlap, "bucketed_loco_quantized_reduce_scatter", int4_loco)
    exact, _ = _engine_losses_with({}, 2, n_steps=10)
    plain, _ = _engine_losses_with({"zero_quantized_gradients": True}, 2, n_steps=10)
    loco, _ = _engine_losses_with(
        {
            "zero_quantized_gradients": True,
            "zeropp_loco_param": {"err_beta": 0.6, "reset_T": 1024},
        },
        2,
        n_steps=10,
    )
    err_plain = np.mean(np.abs(np.array(plain) - np.array(exact)))
    err_loco = np.mean(np.abs(np.array(loco) - np.array(exact)))
    assert traced["plain"] >= 1 and traced["loco"] >= 1, traced
    assert np.isfinite(loco).all()
    # int4 noise in the loss is ~1e-3 here, a hundred times float noise
    assert err_plain > 1e-4
    assert err_loco < err_plain, f"loco {err_loco} not tighter than plain {err_plain}"


def test_loco_without_qgz_raises(devices8):
    """zeropp_loco_param without zero_quantized_gradients must fail loudly
    (round-3 'dead knob' finding) instead of being silently ignored."""
    params = make_mlp_params(jax.random.key(0))
    with pytest.raises(ValueError, match="zeropp_loco_param"):
        deepspeed_tpu.initialize(
            model=mlp_loss_fn,
            model_parameters=params,
            config={
                "train_micro_batch_size_per_gpu": 8,
                "optimizer": {"type": "AdamW", "params": {"lr": LR}},
                "zero_optimization": {
                    "stage": 2,
                    "zeropp_loco_param": {"err_beta": 0.8, "reset_T": 64},
                },
                "mesh": {"data": 8},
                "steps_per_print": 1000,
            },
        )


def test_qwz_trajectory_close_to_exact(devices8):
    """zero_quantized_weights: int8 parameter gather must track the
    full-precision stage-3 trajectory within quantization tolerance."""
    exact, _ = _engine_losses_with({}, 3)
    quant, engine = _engine_losses_with({"zero_quantized_weights": True}, 3)
    assert np.isfinite(quant).all()
    np.testing.assert_allclose(quant, exact, rtol=0.1)
    # params stay sharded over data (stage 3 layout intact)
    leaf = jax.tree_util.tree_leaves(engine.params)[0]
    assert len(leaf.sharding.device_set) == 8
