"""End-to-end engine tests (analogue of reference tests/unit/v1/zero/test_zero.py
stage-correctness-vs-torch and runtime engine tests): every ZeRO stage must
produce the same loss trajectory as a pure-optax reference loop."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import deepspeed_tpu
from deepspeed_tpu.parallel.topology import Topology, set_topology
from deepspeed_tpu.runtime.zero.partition import host_memory_kind

from tests.unit.simple_model import batch_of, make_mlp_params, mlp_loss_fn, random_dataset

LR = 1e-2

# None on a device that exposes a single memory space: offload there is
# numerics-only — placement assertions don't apply
HOST_KIND = host_memory_kind()


def _pure_optax_losses(params, dataset, n_steps, batch_size, gas=1):
    """Reference trajectory: AdamW at fixed LR, averaging grads over gas micro-batches."""
    tx = optax.adamw(LR, weight_decay=0.0)
    state = tx.init(params)
    losses = []
    pos = 0
    for _ in range(n_steps):
        acc = jax.tree.map(jnp.zeros_like, params)
        step_losses = []
        for _ in range(gas):
            batch = batch_of(dataset, pos, batch_size)
            pos += batch_size
            loss, grads = jax.value_and_grad(mlp_loss_fn)(params, batch)
            acc = jax.tree.map(lambda a, g: a + g, acc, grads)
            step_losses.append(float(loss))
        grads = jax.tree.map(lambda g: g / gas, acc)
        updates, state = tx.update(grads, state, params)
        params = optax.apply_updates(params, updates)
        losses.append(np.mean(step_losses))
    return losses


def _engine_losses(stage, dataset, n_steps, gas=1, micro=8, dtype_section=None, mesh=None):
    params = make_mlp_params(jax.random.key(0))
    config = {
        "train_micro_batch_size_per_gpu": micro // 8 if micro >= 8 else 1,
        "gradient_accumulation_steps": gas,
        "optimizer": {"type": "AdamW", "params": {"lr": LR}},
        "zero_optimization": {"stage": stage, "param_persistence_threshold": 0},
        "steps_per_print": 1000,
    }
    if dtype_section:
        config.update(dtype_section)
    if mesh:
        config["mesh"] = mesh
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=mlp_loss_fn, model_parameters=params, config=config
    )
    losses = []
    pos = 0
    for _ in range(n_steps):
        batch = batch_of(dataset, pos, micro * gas)
        pos += micro * gas
        loss = engine.train_batch(batch=batch)
        losses.append(float(loss))
    return losses, engine


@pytest.mark.parametrize("stage", [0, 1, 2, 3])
def test_zero_stage_matches_optax(stage, devices8):
    """Each ZeRO stage is numerically a sharding assignment: trajectories must
    match the unsharded optax reference."""
    dataset = random_dataset(n=512)
    params = make_mlp_params(jax.random.key(0))
    ref = _pure_optax_losses(params, dataset, n_steps=5, batch_size=8)
    got, engine = _engine_losses(stage, dataset, n_steps=5)
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-5)
    assert engine.zero_optimization_stage() == stage


@pytest.mark.parametrize("stage", [2, 3])
def test_zero_sharded_state(stage, devices8):
    """Optimizer state (and stage-3 params) must actually be sharded over data."""
    dataset = random_dataset(n=512)
    _, engine = _engine_losses(stage, dataset, n_steps=1)
    master = engine.opt_state.master
    big_leaf = master["layer_0"]["w"]  # 16x16, divisible by 8
    assert not big_leaf.sharding.is_fully_replicated, f"stage {stage} master should be sharded"
    if stage >= 3:
        p = engine.params["layer_0"]["w"]
        assert not p.sharding.is_fully_replicated, "stage 3 params should be sharded"
    else:
        p = engine.params["layer_0"]["w"]
        assert p.sharding.is_fully_replicated, "stage <3 params should be replicated"


def test_gradient_accumulation_matches(devices8):
    """gas=4 with micro=2 must equal gas=1 with batch=8 reference semantics."""
    dataset = random_dataset(n=512)
    params = make_mlp_params(jax.random.key(0))
    ref = _pure_optax_losses(params, dataset, n_steps=4, batch_size=2, gas=4)
    got, _ = _engine_losses(1, dataset, n_steps=4, gas=4, micro=2)
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-5)


def test_imperative_forward_backward_step(devices8):
    """The reference imperative API: loss = engine(batch); engine.backward(loss);
    engine.step() — must match train_batch."""
    dataset = random_dataset(n=512)
    params = make_mlp_params(jax.random.key(0))
    config = {
        "train_micro_batch_size_per_gpu": 1,
        "gradient_accumulation_steps": 2,
        "optimizer": {"type": "AdamW", "params": {"lr": LR}},
        "zero_optimization": {"stage": 2},
        "steps_per_print": 1000,
    }
    engine, opt, _, _ = deepspeed_tpu.initialize(model=mlp_loss_fn, model_parameters=params, config=config)
    ref = _pure_optax_losses(params, dataset, n_steps=3, batch_size=8, gas=2)
    losses = []
    pos = 0
    for step in range(3):
        step_losses = []
        for micro in range(2):
            batch = batch_of(dataset, pos, 8)
            pos += 8
            loss = engine(batch)
            engine.backward(loss)
            engine.step()
            step_losses.append(float(loss))
        losses.append(np.mean(step_losses))
        assert engine.global_steps == step + 1
    np.testing.assert_allclose(losses, ref, rtol=2e-4, atol=2e-5)


def test_fp16_loss_scale_overflow_skip(devices8):
    """Inject an inf into the batch: the step must be skipped (params
    unchanged) and the dynamic loss scale halved."""
    params = make_mlp_params(jax.random.key(0), dtype=jnp.float16)
    config = {
        "train_micro_batch_size_per_gpu": 1,
        "optimizer": {"type": "Adam", "params": {"lr": LR}},
        "fp16": {"enabled": True, "initial_scale_power": 4, "hysteresis": 1},
        "steps_per_print": 1000,
    }
    engine, _, _, _ = deepspeed_tpu.initialize(model=mlp_loss_fn, model_parameters=params, config=config)
    before = jax.tree.map(np.asarray, engine.params)
    scale_before = float(engine.scaler_state.scale)
    bad = {"x": np.full((8, 16), np.inf, np.float32), "y": np.zeros((8, 16), np.float32)}
    engine.train_batch(batch=bad)
    after = jax.tree.map(np.asarray, engine.params)
    for a, b in zip(jax.tree_util.tree_leaves(before), jax.tree_util.tree_leaves(after)):
        np.testing.assert_array_equal(a, b)
    assert float(engine.scaler_state.scale) == scale_before / 2
    # good step afterwards must apply
    good = {"x": np.ones((8, 16), np.float32), "y": np.zeros((8, 16), np.float32)}
    engine.train_batch(batch=good)
    after2 = jax.tree_util.tree_leaves(jax.tree.map(np.asarray, engine.params))
    changed = any(not np.array_equal(a, b) for a, b in zip(jax.tree_util.tree_leaves(after), after2))
    assert changed, "good step after overflow should update params"


def test_bf16_training_runs(devices8):
    dataset = random_dataset(n=512)
    params = make_mlp_params(jax.random.key(0))
    config = {
        "train_micro_batch_size_per_gpu": 1,
        "optimizer": {"type": "AdamW", "params": {"lr": LR}},
        "zero_optimization": {"stage": 2},
        "bf16": {"enabled": True},
        "steps_per_print": 1000,
    }
    engine, _, _, _ = deepspeed_tpu.initialize(model=mlp_loss_fn, model_parameters=params, config=config)
    fixed = batch_of(dataset, 0, 8)
    losses = [float(engine.train_batch(batch=fixed)) for _ in range(8)]
    assert losses[-1] < losses[0], f"bf16 loss on a fixed batch should decrease: {losses}"
    assert engine.params["layer_0"]["w"].dtype == jnp.bfloat16
    assert engine.opt_state.master["layer_0"]["w"].dtype == jnp.float32


def test_gradient_clipping(devices8):
    dataset = random_dataset(n=512)
    params = make_mlp_params(jax.random.key(0))
    config = {
        "train_micro_batch_size_per_gpu": 1,
        "optimizer": {"type": "AdamW", "params": {"lr": LR}},
        "gradient_clipping": 1e-6,
        "steps_per_print": 1000,
    }
    engine, _, _, _ = deepspeed_tpu.initialize(model=mlp_loss_fn, model_parameters=params, config=config)
    before = jax.tree.map(np.asarray, engine.params)
    engine.train_batch(batch=batch_of(dataset, 0, 8))
    after = jax.tree.map(np.asarray, engine.params)
    # tiny clip → updates bounded; check max param delta is tiny but nonzero
    deltas = [np.abs(a - b).max() for a, b in zip(jax.tree_util.tree_leaves(before), jax.tree_util.tree_leaves(after))]
    assert 0 < max(deltas) < 1e-2


def test_lr_scheduler_warmup(devices8):
    dataset = random_dataset(n=512)
    params = make_mlp_params(jax.random.key(0))
    config = {
        "train_micro_batch_size_per_gpu": 1,
        "optimizer": {"type": "AdamW", "params": {"lr": 0.1}},
        "scheduler": {
            "type": "WarmupLR",
            "params": {"warmup_min_lr": 0.0, "warmup_max_lr": 0.1, "warmup_num_steps": 10, "warmup_type": "linear"},
        },
        "steps_per_print": 1000,
    }
    engine, _, _, sched = deepspeed_tpu.initialize(model=mlp_loss_fn, model_parameters=params, config=config)
    assert sched is not None
    engine.train_batch(batch=batch_of(dataset, 0, 8))
    lr1 = engine.get_lr()[0]
    engine.train_batch(batch=batch_of(dataset, 8, 8))
    lr2 = engine.get_lr()[0]
    assert 0 <= lr1 < lr2 < 0.1


def test_dataloader_integration(devices8):
    dataset = random_dataset(n=64)
    params = make_mlp_params(jax.random.key(0))
    config = {
        "train_micro_batch_size_per_gpu": 1,
        "optimizer": {"type": "AdamW", "params": {"lr": LR}},
        "steps_per_print": 1000,
    }
    engine, _, loader, _ = deepspeed_tpu.initialize(
        model=mlp_loss_fn, model_parameters=params, config=config, training_data=dataset
    )
    assert loader is not None and len(loader) == 8
    for batch in loader:
        loss = engine.train_batch(batch=batch)
        break
    assert np.isfinite(float(loss))


def test_eval_batch(devices8):
    dataset = random_dataset(n=64)
    params = make_mlp_params(jax.random.key(0))
    config = {
        "train_micro_batch_size_per_gpu": 1,
        "optimizer": {"type": "AdamW", "params": {"lr": LR}},
        "steps_per_print": 1000,
    }
    engine, _, _, _ = deepspeed_tpu.initialize(model=mlp_loss_fn, model_parameters=params, config=config)
    loss = engine.eval_batch(batch_of(dataset, 0, 8))
    assert np.isfinite(float(loss))


@pytest.mark.parametrize("opt_type", ["Adam", "Lamb", "Lion", "Adagrad", "SGD", "Muon", "OneBitAdam"])
def test_optimizer_zoo(opt_type, devices8):
    dataset = random_dataset(n=512)
    params = make_mlp_params(jax.random.key(0))
    config = {
        "train_micro_batch_size_per_gpu": 1,
        "optimizer": {"type": opt_type, "params": {"lr": 1e-3}},
        # OneBitAdam's compressed exchange needs replicated momentum (stage 0)
        "zero_optimization": {"stage": 0 if opt_type == "OneBitAdam" else 1},
        "steps_per_print": 1000,
    }
    engine, _, _, _ = deepspeed_tpu.initialize(model=mlp_loss_fn, model_parameters=params, config=config)
    fixed = batch_of(dataset, 0, 8)
    losses = [float(engine.train_batch(batch=fixed)) for _ in range(8)]
    assert losses[-1] < losses[0], f"{opt_type} loss on a fixed batch should decrease: {losses}"


class TestZeroOffload:
    """ZeRO-Offload tier (VERDICT missing #1): optimizer state in pinned_host
    memory, update computed on the host CPU; trajectory must match the
    non-offloaded run exactly."""

    def _offload_losses(self, stage, dataset, n_steps, offload_param=False):
        params = make_mlp_params(jax.random.key(0))
        zero = {"stage": stage, "param_persistence_threshold": 0,
                "offload_optimizer": {"device": "cpu", "pin_memory": True}}
        if offload_param:
            zero["offload_param"] = {"device": "cpu"}
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=mlp_loss_fn,
            model_parameters=params,
            config={
                "train_micro_batch_size_per_gpu": 1,
                "optimizer": {"type": "AdamW", "params": {"lr": LR}},
                "zero_optimization": zero,
                "steps_per_print": 1000,
            },
        )
        losses = []
        pos = 0
        for _ in range(n_steps):
            batch = batch_of(dataset, pos, 8)
            pos += 8
            losses.append(float(engine.train_batch(batch=batch)))
        return losses, engine

    @pytest.mark.parametrize("stage", [1, 2, 3])
    def test_offload_trajectory_matches_optax(self, stage, devices8):
        dataset = random_dataset(n=512)
        params = make_mlp_params(jax.random.key(0))
        ref = _pure_optax_losses(params, dataset, n_steps=5, batch_size=8)
        got, engine = self._offload_losses(stage, dataset, n_steps=5)
        np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-5)
        # optimizer state actually lives in host memory
        if HOST_KIND is not None:
            master_leaf = engine.opt_state.master["layer_0"]["w"]
            assert master_leaf.sharding.memory_kind == HOST_KIND
            # params stay in device memory
            assert engine.params["layer_0"]["w"].sharding.memory_kind == "device"

    def test_offload_param_tier(self, devices8):
        """offload_param: params also live in pinned_host between steps."""
        dataset = random_dataset(n=512)
        params = make_mlp_params(jax.random.key(0))
        ref = _pure_optax_losses(params, dataset, n_steps=3, batch_size=8)
        got, engine = self._offload_losses(3, dataset, n_steps=3, offload_param=True)
        np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-5)
        if HOST_KIND is not None:
            assert engine.params["layer_0"]["w"].sharding.memory_kind == HOST_KIND

    def test_nvme_pluggable_writer_roundtrip(self, tmp_path, devices8):
        """Regression: host-tier state saved through a pluggable checkpoint
        writer (flat leaf list on disk) must restore."""
        dataset = random_dataset(n=512)
        params = make_mlp_params(jax.random.key(0))

        def build(nvme):
            engine, _, _, _ = deepspeed_tpu.initialize(
                model=mlp_loss_fn,
                model_parameters=params,
                config={
                    "train_micro_batch_size_per_gpu": 1,
                    "optimizer": {"type": "AdamW", "params": {"lr": LR}},
                    "checkpoint": {"writer": "sync"},
                    "zero_optimization": {
                        "stage": 1,
                        "offload_optimizer": {"device": "nvme", "nvme_path": str(nvme)},
                    },
                    "steps_per_print": 1000,
                },
            )
            return engine

        engine = build(tmp_path / "n1")
        for i in range(2):
            engine.train_batch(batch=batch_of(dataset, i * 8, 8))
        engine.save_checkpoint(str(tmp_path / "ck"), tag="w")
        cont = [float(engine.train_batch(batch=batch_of(dataset, 16 + i * 8, 8)))
                for i in range(2)]
        engine2 = build(tmp_path / "n2")
        engine2.load_checkpoint(str(tmp_path / "ck"), tag="w")
        resumed = [float(engine2.train_batch(batch=batch_of(dataset, 16 + i * 8, 8)))
                   for i in range(2)]
        np.testing.assert_allclose(resumed, cont, rtol=1e-5, atol=1e-6)

    def test_offload_checkpoint_roundtrip(self, tmp_path, devices8):
        """Offloaded state survives save/load (orbax handles host arrays)."""
        dataset = random_dataset(n=512)
        _, engine = self._offload_losses(2, dataset, n_steps=2)
        engine.save_checkpoint(str(tmp_path), tag="off")
        before = np.asarray(
            jax.device_get(engine.opt_state.master["layer_0"]["w"])
        )
        _, engine2 = self._offload_losses(2, dataset, n_steps=1)
        engine2.load_checkpoint(str(tmp_path), tag="off")
        after = np.asarray(jax.device_get(engine2.opt_state.master["layer_0"]["w"]))
        np.testing.assert_allclose(before, after, rtol=0, atol=0)
        if HOST_KIND is not None:
            assert engine2.opt_state.master["layer_0"]["w"].sharding.memory_kind == HOST_KIND


class TestSuperOffloadTwinFlow:
    """SuperOffload (host-RAM resident optimizer, reference
    superoffload_stage3.py) and Twin-Flow partial offload (reference
    engine.py:921 zero_partial_offload)."""

    def test_superoffload_trajectory_matches_optax(self, devices8):
        dataset = random_dataset(n=512)
        params = make_mlp_params(jax.random.key(0))
        ref = _pure_optax_losses(params, dataset, n_steps=5, batch_size=8)
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=mlp_loss_fn,
            model_parameters=params,
            config={
                "train_micro_batch_size_per_gpu": 1,
                "optimizer": {"type": "AdamW", "params": {"lr": LR}},
                "zero_optimization": {
                    "stage": 2,
                    "offload_optimizer": {"device": "cpu", "super_offload": True},
                },
                "steps_per_print": 1000,
            },
        )
        from deepspeed_tpu.runtime.superoffload import SuperOffloadHostOptimizer

        assert isinstance(engine._host_opt, SuperOffloadHostOptimizer)
        got = []
        pos = 0
        for _ in range(5):
            got.append(float(engine.train_batch(batch=batch_of(dataset, pos, 8))))
            pos += 8
        np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-5)
        # state is RAM-resident numpy, not jax
        assert engine.opt_state == {}
        assert all(isinstance(v, np.ndarray) for v in engine._host_opt._state.values())

    def test_twinflow_partial_ratio_mixes_memory_kinds(self, devices8):
        dataset = random_dataset(n=512)
        params = make_mlp_params(jax.random.key(0))
        ref = _pure_optax_losses(params, dataset, n_steps=3, batch_size=8)
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=mlp_loss_fn,
            model_parameters=params,
            config={
                "train_micro_batch_size_per_gpu": 1,
                "optimizer": {"type": "AdamW", "params": {"lr": LR}},
                "zero_optimization": {
                    "stage": 2,
                    "offload_optimizer": {"device": "cpu", "ratio": 0.4},
                },
                "steps_per_print": 1000,
            },
        )
        kinds = {
            s.memory_kind
            for s in jax.tree.leaves(engine._state_shardings)
        }
        if HOST_KIND is not None:
            assert HOST_KIND in kinds and "device" in kinds, kinds
        got = []
        pos = 0
        for _ in range(3):
            got.append(float(engine.train_batch(batch=batch_of(dataset, pos, 8))))
            pos += 8
        np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-5)


class TestNVMeOffload:
    """ZeRO-Infinity optimizer tier: fp32 master + moments in NVMe files,
    pipelined swap around a native CPU-Adam step (runtime/swap_tensor.py;
    reference swap_tensor/partitioned_optimizer_swapper.py)."""

    def _nvme_losses(self, stage, dataset, n_steps, nvme_dir, engine_out=None):
        params = make_mlp_params(jax.random.key(0))
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=mlp_loss_fn,
            model_parameters=params,
            config={
                "train_micro_batch_size_per_gpu": 1,
                "optimizer": {"type": "AdamW", "params": {"lr": LR}},
                "zero_optimization": {
                    "stage": stage,
                    "param_persistence_threshold": 0,
                    "offload_optimizer": {"device": "nvme", "nvme_path": str(nvme_dir)},
                },
                "steps_per_print": 1000,
            },
        )
        losses = []
        pos = 0
        for _ in range(n_steps):
            batch = batch_of(dataset, pos, 8)
            pos += 8
            losses.append(float(engine.train_batch(batch=batch)))
        return losses, engine

    @pytest.mark.parametrize("stage", [1, 3])
    def test_nvme_trajectory_matches_optax(self, stage, tmp_path, devices8):
        dataset = random_dataset(n=512)
        params = make_mlp_params(jax.random.key(0))
        ref = _pure_optax_losses(params, dataset, n_steps=5, batch_size=8)
        got, engine = self._nvme_losses(stage, dataset, n_steps=5, nvme_dir=tmp_path)
        np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-5)
        # the state REALLY lives on disk: swap files exist, no jax opt state
        import os

        swap_dir = engine._host_opt.swapper.swap_dir
        files = os.listdir(swap_dir)
        assert any(f.endswith(".master.swp") for f in files)
        assert any(f.endswith(".exp_avg.swp") for f in files)
        assert engine.opt_state == {}

    def test_nvme_unpipelined_swapper_correct(self, tmp_path, devices8):
        """pipeline_read/write=False must still read every leaf's state
        (regression: un-prefetched leaves once ran Adam on empty buffers)."""
        from deepspeed_tpu.runtime.swap_tensor import NVMeOptimizerSwapper

        rng = np.random.default_rng(0)
        leaves = [("a", rng.normal(size=(32, 16)).astype(np.float32)),
                  ("b", rng.normal(size=(64,)).astype(np.float32)),
                  ("c", rng.normal(size=(8, 8)).astype(np.float32))]
        grads = [(n, np.ones_like(v)) for n, v in leaves]
        sw_pip = NVMeOptimizerSwapper(str(tmp_path / "p"), lr=1e-2)
        sw_seq = NVMeOptimizerSwapper(str(tmp_path / "s"), lr=1e-2,
                                      pipeline_read=False, pipeline_write=False)
        sw_pip.init_from_params(leaves)
        sw_seq.init_from_params(leaves)
        for _ in range(3):
            out_p = sw_pip.step(grads)
            out_s = sw_seq.step(grads)
        for n, _ in leaves:
            np.testing.assert_allclose(out_p[n], out_s[n], rtol=1e-6, atol=1e-7)

    def test_nvme_without_path_falls_back(self, devices8):
        """device=nvme with no nvme_path must warn and train via the
        pinned-host tier, not crash (pre-NVMe configs keep working)."""
        dataset = random_dataset(n=512)
        params = make_mlp_params(jax.random.key(0))
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=mlp_loss_fn,
            model_parameters=params,
            config={
                "train_micro_batch_size_per_gpu": 1,
                "optimizer": {"type": "AdamW", "params": {"lr": LR}},
                "zero_optimization": {
                    "stage": 2,
                    "offload_optimizer": {"device": "nvme"},
                },
                "steps_per_print": 1000,
            },
        )
        assert engine._host_opt is None
        assert engine.plan.offload_optimizer  # pinned-host tier active
        loss = float(engine.train_batch(batch=batch_of(dataset, 0, 8)))
        assert np.isfinite(loss)

    def test_nvme_checkpoint_roundtrip(self, tmp_path, devices8):
        dataset = random_dataset(n=512)
        nvme1 = tmp_path / "nvme1"
        nvme2 = tmp_path / "nvme2"
        ckpt = tmp_path / "ckpt"
        _, engine = self._nvme_losses(1, dataset, n_steps=2, nvme_dir=nvme1)
        engine.save_checkpoint(str(ckpt), tag="nv")
        cont = []
        pos = 16
        for _ in range(2):
            cont.append(float(engine.train_batch(batch=batch_of(dataset, pos, 8))))
            pos += 8
        # fresh engine, different nvme dir, resume from checkpoint
        _, engine2 = self._nvme_losses(1, dataset, n_steps=0, nvme_dir=nvme2)
        engine2.load_checkpoint(str(ckpt), tag="nv")
        resumed = []
        pos = 16
        for _ in range(2):
            resumed.append(float(engine2.train_batch(batch=batch_of(dataset, pos, 8))))
            pos += 8
        np.testing.assert_allclose(resumed, cont, rtol=1e-5, atol=1e-6)

