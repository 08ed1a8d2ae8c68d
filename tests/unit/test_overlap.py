"""Bucketed ZeRO-3 comm/compute overlap tests: bucket assembly, scan-chunk
selection, bitwise parity of the bucketed wire collectives against their
per-leaf counterparts, engine-level loss parity with the overlap escape
hatch (``overlap_comm: false``), chunked-scan forward/grad parity, the
streamed-Adam double buffer, and the v2 split-step cache donation."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import deepspeed_tpu
from deepspeed_tpu.runtime.zero.overlap import (
    assign_buckets,
    bucketed_all_gather,
    bucketed_loco_quantized_reduce_scatter,
    bucketed_psum_scatter,
    bucketed_quantized_all_gather,
    bucketed_quantized_reduce_scatter,
    overlap_chunk,
)

from tests.unit.simple_model import batch_of, make_mlp_params, mlp_loss_fn, random_dataset

LR = 1e-2
W = 8


def _mesh8():
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:8]), ("data",))


def _spec_at(k, ndim):
    parts = [None] * ndim
    parts[k] = "data"
    return P(*parts)


# ---------------------------------------------------------------------------
# bucket assembly
# ---------------------------------------------------------------------------
class TestAssignBuckets:
    def test_every_leaf_exactly_once_in_order(self):
        sizes = [3, 9, 1, 14, 2, 2, 8, 100, 1]
        buckets = assign_buckets(sizes, 10)
        flat = [i for b in buckets for i in b]
        assert flat == list(range(len(sizes)))  # exactly once, order preserved

    def test_byte_target_respected(self):
        rng = np.random.default_rng(0)
        sizes = rng.integers(1, 40, size=64).tolist()
        target = 64
        for b in assign_buckets(sizes, target):
            total = sum(sizes[i] for i in b)
            # a bucket only exceeds the target when a single leaf does
            assert total <= target or len(b) == 1

    def test_oversized_leaf_gets_own_bucket(self):
        assert assign_buckets([4, 100, 4], 10) == [[0], [1], [2]]

    def test_greedy_packing(self):
        assert assign_buckets([4, 4, 4, 4], 8) == [[0, 1], [2, 3]]

    def test_nonpositive_target_is_per_leaf(self):
        assert assign_buckets([5, 5, 5], 0) == [[0], [1], [2]]

    def test_empty(self):
        assert assign_buckets([], 10) == []


class TestOverlapChunk:
    def test_largest_fitting_divisor(self):
        assert overlap_chunk(8, 100, 400) == 4

    def test_caps_at_max_chunk(self):
        assert overlap_chunk(16, 1, 1 << 30, max_chunk=4) == 4

    def test_floors_at_smallest_divisor_when_nothing_fits(self):
        # prefetch window too small for even 2 layers: still chunk by 2 —
        # depth-1 prefetch is the point of overlap
        assert overlap_chunk(8, 100, 50) == 2

    def test_prime_depth_falls_back_to_plain_scan(self):
        assert overlap_chunk(13, 100, 1 << 30, max_chunk=8) == 1

    def test_degenerate(self):
        assert overlap_chunk(1, 100, 1 << 30) == 1
        assert overlap_chunk(8, 0, 1 << 30) == 1


# ---------------------------------------------------------------------------
# bucketed collectives are bitwise-identical to the per-leaf exchanges
# ---------------------------------------------------------------------------
_SHAPES_DIMS = [((16, 5), 0), ((3, 24), 1), ((8,), 0)]


def _rank_varied(key, shape):
    """[W, *shape] stacked per-rank inputs, different on every rank."""
    return jax.random.normal(key, (W,) + shape, jnp.float32)


def _stacked_inputs(seed=0):
    keys = jax.random.split(jax.random.key(seed), len(_SHAPES_DIMS))
    return [_rank_varied(k, s) for k, (s, _) in zip(keys, _SHAPES_DIMS)]


class TestBucketedBitwise:
    def test_quantized_reduce_scatter_matches_per_leaf(self, devices8):
        from deepspeed_tpu.ops.quantizer.block_quant import quantized_reduce_scatter_along

        mesh = _mesh8()
        dims = [k for _, k in _SHAPES_DIMS]
        out_spec = tuple(_spec_at(k, len(s)) for s, k in _SHAPES_DIMS)

        def run(*stacked):
            loc = [x[0] for x in stacked]
            fused = bucketed_quantized_reduce_scatter(loc, dims, "data", block_size=4)
            per = [
                quantized_reduce_scatter_along(x, "data", k, block_size=4)
                for x, k in zip(loc, dims)
            ]
            return tuple(fused), tuple(per)

        fn = jax.jit(jax.shard_map(
            run, mesh=mesh, in_specs=(P("data"),) * len(dims),
            out_specs=(out_spec, out_spec), axis_names={"data"}, check_vma=False,
        ))
        fused, per = fn(*_stacked_inputs())
        for a, b in zip(fused, per):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_loco_reduce_scatter_matches_per_leaf(self, devices8):
        from deepspeed_tpu.ops.quantizer.block_quant import loco_quantized_reduce_scatter_along

        mesh = _mesh8()
        dims = [k for _, k in _SHAPES_DIMS]
        out_spec = tuple(_spec_at(k, len(s)) for s, k in _SHAPES_DIMS)
        err_spec = (P("data"),) * len(dims)
        xs = _stacked_inputs(1)
        errs = [0.1 * x for x in _stacked_inputs(2)]

        def run(*args):
            stacked, stacked_e = args[: len(dims)], args[len(dims):]
            loc = [x[0] for x in stacked]
            le = [e[0] for e in stacked_e]
            fused, fe = bucketed_loco_quantized_reduce_scatter(
                loc, le, dims, "data", block_size=4, err_beta=0.8
            )
            per, pe = [], []
            for x, e, k in zip(loc, le, dims):
                o, e2 = loco_quantized_reduce_scatter_along(
                    x, e, "data", k, block_size=4, err_beta=0.8
                )
                per.append(o)
                pe.append(e2)
            return (tuple(fused), tuple(x[None] for x in fe),
                    tuple(per), tuple(x[None] for x in pe))

        fn = jax.jit(jax.shard_map(
            run, mesh=mesh, in_specs=(P("data"),) * (2 * len(dims)),
            out_specs=(out_spec, err_spec, out_spec, err_spec),
            axis_names={"data"}, check_vma=False,
        ))
        fused, fe, per, pe = fn(*xs, *errs)
        for a, b in zip(fused, per):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(fe, pe):  # error-feedback state must also agree
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_quantized_all_gather_matches_per_leaf(self, devices8):
        from deepspeed_tpu.ops.quantizer.block_quant import quantized_all_gather_along

        mesh = _mesh8()
        # local dim-k shards; gather reassembles k*W
        shapes_dims = [((2, 5), 0), ((3, 2), 1), ((1,), 0)]
        dims = [k for _, k in shapes_dims]
        rep = tuple(P(*([None] * len(s))) for s, _ in shapes_dims)
        keys = jax.random.split(jax.random.key(3), len(shapes_dims))
        xs = [_rank_varied(k, s) for k, (s, _) in zip(keys, shapes_dims)]

        def run(*stacked):
            loc = [x[0] for x in stacked]
            fused = bucketed_quantized_all_gather(loc, dims, "data", block_size=4)
            per = [
                quantized_all_gather_along(x, "data", k, block_size=4)
                for x, k in zip(loc, dims)
            ]
            return tuple(fused), tuple(per)

        fn = jax.jit(jax.shard_map(
            run, mesh=mesh, in_specs=(P("data"),) * len(dims),
            out_specs=(rep, rep), axis_names={"data"}, check_vma=False,
        ))
        fused, per = fn(*xs)
        for a, b in zip(fused, per):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_plain_all_gather_matches_per_leaf(self, devices8):
        mesh = _mesh8()
        shapes_dims = [((2, 5), 0), ((3, 2), 1), ((1,), 0)]
        dims = [k for _, k in shapes_dims]
        rep = tuple(P(*([None] * len(s))) for s, _ in shapes_dims)
        keys = jax.random.split(jax.random.key(4), len(shapes_dims))
        xs = [_rank_varied(k, s) for k, (s, _) in zip(keys, shapes_dims)]

        def run(*stacked):
            loc = [x[0] for x in stacked]
            fused = bucketed_all_gather(loc, dims, "data")
            per = [jax.lax.all_gather(x, "data", axis=k, tiled=True)
                   for x, k in zip(loc, dims)]
            return tuple(fused), tuple(per)

        fn = jax.jit(jax.shard_map(
            run, mesh=mesh, in_specs=(P("data"),) * len(dims),
            out_specs=(rep, rep), axis_names={"data"}, check_vma=False,
        ))
        fused, per = fn(*xs)
        for a, b in zip(fused, per):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_psum_scatter_matches_per_leaf(self, devices8):
        mesh = _mesh8()
        dims = [k for _, k in _SHAPES_DIMS]
        out_spec = tuple(_spec_at(k, len(s)) for s, k in _SHAPES_DIMS)

        def run(*stacked):
            loc = [x[0] for x in stacked]
            fused = bucketed_psum_scatter(loc, dims, "data")
            per = [
                jax.lax.psum_scatter(x, "data", scatter_dimension=k, tiled=True) / W
                for x, k in zip(loc, dims)
            ]
            return tuple(fused), tuple(per)

        fn = jax.jit(jax.shard_map(
            run, mesh=mesh, in_specs=(P("data"),) * len(dims),
            out_specs=(out_spec, out_spec), axis_names={"data"}, check_vma=False,
        ))
        fused, per = fn(*_stacked_inputs(5))
        for a, b in zip(fused, per):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)


# ---------------------------------------------------------------------------
# engine loss parity: overlap on (default) vs off (escape hatch)
# ---------------------------------------------------------------------------
def _engine_losses(stage, extra, overlap, n_steps=6):
    dataset = random_dataset(n=64 * n_steps)
    params = make_mlp_params(jax.random.key(0))
    zcfg = {"stage": stage, "param_persistence_threshold": 0}
    zcfg.update(extra)
    if overlap is not None:
        zcfg["overlap_comm"] = overlap
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
    return losses


class TestOverlapParity:
    def test_stage3_plain(self, devices8):
        """ZeRO-3 full-precision: the bucketed gather/scatter (default) and
        the per-leaf escape hatch must produce the same training losses."""
        on = _engine_losses(3, {}, None)
        off = _engine_losses(3, {}, False)
        assert np.isfinite(on).all()
        np.testing.assert_allclose(on, off, rtol=0, atol=1e-6)

    def test_stage3_qgz(self, devices8, monkeypatch):
        from deepspeed_tpu.runtime.engine import DeepSpeedEngine

        monkeypatch.setattr(DeepSpeedEngine, "QGZ_MIN_SIZE", 0)
        extra = {"zero_quantized_gradients": True}
        on = _engine_losses(3, extra, None)
        off = _engine_losses(3, extra, False)
        assert np.isfinite(on).all()
        np.testing.assert_allclose(on, off, rtol=0, atol=1e-6)

    def test_stage3_qwz(self, devices8, monkeypatch):
        from deepspeed_tpu.runtime.engine import DeepSpeedEngine

        monkeypatch.setattr(DeepSpeedEngine, "QGZ_MIN_SIZE", 0)
        extra = {"zero_quantized_weights": True}
        on = _engine_losses(3, extra, None)
        off = _engine_losses(3, extra, False)
        assert np.isfinite(on).all()
        np.testing.assert_allclose(on, off, rtol=0, atol=1e-6)

    def test_stage2_qgz_loco(self, devices8, monkeypatch):
        """LoCo error feedback: bucketing must not perturb the error-buffer
        trajectory (residual/EMA stay per-leaf; only the wire is fused)."""
        from deepspeed_tpu.runtime.engine import DeepSpeedEngine

        monkeypatch.setattr(DeepSpeedEngine, "QGZ_MIN_SIZE", 0)
        extra = {
            "zero_quantized_gradients": True,
            "zeropp_loco_param": {"err_beta": 0.8, "reset_T": 1024},
        }
        on = _engine_losses(2, extra, None)
        off = _engine_losses(2, extra, False)
        assert np.isfinite(on).all()
        np.testing.assert_allclose(on, off, rtol=0, atol=1e-6)


# ---------------------------------------------------------------------------
# chunked layer scan (bucketed parameter prefetch)
# ---------------------------------------------------------------------------
class TestChunkedScan:
    def test_forward_and_grads_match_plain_scan(self):
        from deepspeed_tpu.models import get_config, init_params, make_loss_fn
        from deepspeed_tpu.models.transformer import overlap_scan

        cfg = get_config("tiny", n_layers=4)
        params = init_params(cfg, jax.random.key(0))
        loss_fn = make_loss_fn(cfg)
        toks = np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 32)).astype(np.int32)
        batch = {"input_ids": toks}

        grad_fn = jax.jit(jax.value_and_grad(loss_fn))
        l1, g1 = grad_fn(params, batch)
        with overlap_scan(2):
            l2, g2 = jax.jit(jax.value_and_grad(loss_fn))(params, batch)
        np.testing.assert_allclose(float(l1), float(l2), rtol=0, atol=1e-6)
        for a, b in zip(jax.tree_util.tree_leaves(g1), jax.tree_util.tree_leaves(g2)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)

    def test_non_divisible_chunk_falls_back(self):
        from deepspeed_tpu.models import get_config, init_params, make_loss_fn
        from deepspeed_tpu.models.transformer import overlap_scan

        cfg = get_config("tiny", n_layers=3)
        params = init_params(cfg, jax.random.key(0))
        loss_fn = make_loss_fn(cfg)
        toks = np.random.default_rng(1).integers(0, cfg.vocab_size, (2, 16)).astype(np.int32)
        l1 = float(jax.jit(loss_fn)(params, {"input_ids": toks}))
        with overlap_scan(2):  # 2 does not divide 3: plain scan
            l2 = float(jax.jit(loss_fn)(params, {"input_ids": toks}))
        np.testing.assert_allclose(l1, l2, rtol=0, atol=1e-6)


# ---------------------------------------------------------------------------
# streamed-Adam double buffer
# ---------------------------------------------------------------------------
class TestStreamedDoubleBuffer:
    def _force_streaming(self, monkeypatch):
        from deepspeed_tpu.runtime import streamed_adam as sa

        # CPU has no pinned_host: fake host placement + identity copies so
        # the chunked fori_loop path runs (the schedule under test)
        monkeypatch.setattr(sa, "_is_host", lambda x: True)
        monkeypatch.setattr(sa, "_to_dev", lambda x: x)
        monkeypatch.setattr(sa, "_to_host", lambda x: x)
        return sa

    def test_leaf_double_buffer_bitwise(self, monkeypatch):
        sa = self._force_streaming(monkeypatch)
        rng = np.random.default_rng(0)
        shape = (32, 16)  # dim0 % 8 == 0 keeps the window sublane-aligned
        g = jnp.asarray(rng.normal(size=shape), jnp.float32)
        m = jnp.asarray(rng.normal(size=shape), jnp.float32)
        mu = jnp.asarray(rng.normal(size=shape), jnp.float32)
        nu = jnp.asarray(np.abs(rng.normal(size=shape)), jnp.float32)
        p = m.astype(jnp.bfloat16)
        kw = dict(b1=0.9, b2=0.99, eps=1e-8, wd=0.01, c1=0.1, c2=0.02, chunk=64)
        a = sa.streamed_adamw_leaf(g, m, mu, nu, p, 1e-3, double_buffer=True, **kw)
        b = sa.streamed_adamw_leaf(g, m, mu, nu, p, 1e-3, double_buffer=False, **kw)
        for x, y in zip(a, b):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))

    def test_leaf_q8_double_buffer_bitwise(self, monkeypatch):
        sa = self._force_streaming(monkeypatch)
        rng = np.random.default_rng(1)
        # q8 windows need a 256-aligned minor dim and 32-row chunk granularity
        shape = (64, sa.QUANT_BLOCK)
        g = jnp.asarray(rng.normal(size=shape), jnp.float32)
        m = jnp.asarray(rng.normal(size=shape), jnp.float32)
        mu = sa._q8_mu(jnp.asarray(rng.normal(size=shape), jnp.float32))
        nu = sa._q8_nu(jnp.asarray(np.abs(rng.normal(size=shape)), jnp.float32))
        mu = {"q": mu[0], "s": mu[1]}
        nu = {"q": nu[0], "s": nu[1]}
        p = m.astype(jnp.bfloat16)
        kw = dict(b1=0.9, b2=0.99, eps=1e-8, wd=0.0, c1=0.1, c2=0.02,
                  chunk=32 * sa.QUANT_BLOCK)
        a = sa.streamed_adamw_leaf_q8(g, m, mu, nu, p, 1e-3, double_buffer=True, **kw)
        b = sa.streamed_adamw_leaf_q8(g, m, mu, nu, p, 1e-3, double_buffer=False, **kw)
        for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# v2 split-step cache donation (regression: donate_argnums was (12, 13),
# aliasing the scalar temperature and only ONE of the two cache pools)
# ---------------------------------------------------------------------------
class TestSplitStepDonation:
    def test_both_cache_pools_aliased(self, monkeypatch):
        from deepspeed_tpu.inference.config import RaggedInferenceEngineConfig
        from deepspeed_tpu.inference.v2 import engine_v2 as ev2
        from deepspeed_tpu.models import get_config, init_params

        cfg = get_config("tiny", n_layers=2, dtype="float32", max_seq_len=512)
        params = init_params(cfg, jax.random.key(0))
        rc = RaggedInferenceEngineConfig.from_dict(
            {
                "dtype": "float32",
                "kv_cache": {"block_size": 16, "num_blocks": 64, "max_blocks_per_seq": 8},
                "state_manager": {"max_ragged_batch_size": 64, "max_ragged_sequence_count": 4},
            }
        )

        captured = {}
        orig = ev2.InferenceEngineV2._build_split_step

        def wrapped(self, tq):
            fn = orig(self, tq)

            def call(*args):
                captured.setdefault("fn_args", (fn, args))
                return fn(*args)

            return call

        monkeypatch.setattr(ev2.InferenceEngineV2, "_build_split_step", wrapped)
        engine = ev2.InferenceEngineV2(cfg, params, rc)
        engine.generate([np.arange(1, 9, dtype=np.int32)], max_new_tokens=2)
        assert "fn_args" in captured, "split step never ran"
        fn, args = captured["fn_args"]

        # the cache pools are the step's last argument, (k, v), donated
        # whole, whatever staged inputs precede it
        kc_shape, vc_shape = (pool.shape for pool in args[-1])
        txt = fn.lower(*args).as_text()
        # every donated arg carries tf.aliasing_output in the lowered module;
        # collect the tensor types they annotate
        sig = txt[txt.index("@main("):]
        sig = sig[: sig.index("{\n") if "{\n" in sig else len(sig)]
        aliased = re.findall(r"tensor<([0-9x]+)xf32>\s*\{[^}]*tf\.aliasing_output", sig)
        dims = [tuple(int(d) for d in a.split("x")) for a in aliased]
        assert sorted(dims) == sorted([tuple(kc_shape), tuple(vc_shape)]), (
            f"expected exactly the k/v cache pools {kc_shape}/{vc_shape} "
            f"donated, got {dims}"
        )
