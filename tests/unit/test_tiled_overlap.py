"""Tile-granular compute/collective overlap (comm/overlap_tiled.py): bitwise
parity of the per-tile ppermute rings against the monolithic wires, the
non-divisible fallback, HLO-structural independence of the per-tile
collectives, the comm_overlap config seam through engine_v2 and the ZeRO-3
bucketed gathers, and per-wire tile accounting.

The parity oracles follow the module's numerics contract:

* fp32, comm_quant="none": tiled == monolithic ``lax.psum`` BITWISE.
* bf16, comm_quant="none": tiled == per-tile ``lax.psum`` BITWISE; only
  allclose vs the monolithic psum of the fused GEMM (XLA sinks the dot's
  f32->bf16 convert past its own all-reduce, so the untiled baseline sums
  unrounded f32 values no decomposed collective can observe).
* comm_quant="int8" (fp32 AND bf16): tiled == untiled ``quantized_psum_tp``
  BITWISE at every tile count (global-flat block alignment).

The structural test asserts the T3/Domino property the perf claim rests on:
the lowered program hands XLA >= tp_overlap_tiles collective-permutes with
NO dependency path between them (a max antichain in the permute def-use
order), i.e. peers the latency-hiding scheduler can overlap — a scan-based
decomposition would serialize them behind its loop carry and flunk this.

Heavyweight cases (multi-second shard_map/engine compiles) are marked
``slow``; the tiled-overlap gate in tools/run_smoke.sh runs this file
without the marker filter."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.comm.overlap_tiled import (
    check_comm_overlap,
    check_overlap_tiles,
    effective_tiles,
    peer_chunks,
    tiled_tp_matmul,
)
from deepspeed_tpu.comm.quantized import (
    quantized_psum_tp,
    reset_wire_stats,
    wire_stats,
)
from deepspeed_tpu.parallel.topology import (
    MODEL_AXIS,
    Topology,
    reset_topology,
    set_topology,
)


# ---------------------------------------------------------------------------
# config seam
# ---------------------------------------------------------------------------
class TestCheckCommOverlap:
    def test_valid_modes(self):
        assert check_comm_overlap("none") == "none"
        assert check_comm_overlap("tiled") == "tiled"
        assert check_comm_overlap(None) == "none"  # unset config field

    @pytest.mark.parametrize("bad", ["TILED", "tile", "t3", "yes"])
    def test_typo_raises(self, bad):
        with pytest.raises(ValueError, match="comm_overlap"):
            check_comm_overlap(bad)

    def test_tiles_validation(self):
        assert check_overlap_tiles(2) == 2
        assert check_overlap_tiles(None) == 4  # unset -> default
        with pytest.raises(ValueError, match="tp_overlap_tiles"):
            check_overlap_tiles(0)


class TestEffectiveTiles:
    def test_divisible(self):
        assert effective_tiles(16, 64, 4, 2) == 4

    def test_world_one_is_untiled(self):
        assert effective_tiles(16, 64, 4, 1) == 1

    def test_row_nondivisible_falls_back(self):
        assert effective_tiles(6, 64, 4, 2) == 1

    def test_int8_block_quantum(self):
        # per-tile 4*64=256 == W*block_size: tiles stay whole quant blocks
        assert effective_tiles(16, 64, 4, 2, "int8", block_size=128) == 4
        # per-tile 256 % (2*256) != 0: fallback keeps blocks global-flat
        assert effective_tiles(16, 64, 4, 2, "int8", block_size=256) == 1


class TestPeerChunks:
    def test_splits_and_reassembles(self):
        x = jnp.arange(12.0).reshape(6, 2)
        outs = peer_chunks(lambda c: c * 2, 3, x)
        assert len(outs) == 3
        np.testing.assert_array_equal(
            np.asarray(jnp.concatenate(outs)), np.asarray(x) * 2
        )

    def test_none_passthrough(self):
        x = jnp.ones((4, 2))
        seen = []
        peer_chunks(lambda c, s: seen.append(s), 2, x, None)
        assert seen == [None, None]


# ---------------------------------------------------------------------------
# ring parity vs the monolithic wires
# ---------------------------------------------------------------------------
def _mesh(devices8, world):
    return Mesh(np.array(devices8[:world]), (MODEL_AXIS,))


def _operands(dtype, t=16, k=64, h=64):
    x = jax.random.normal(jax.random.key(0), (t, k), jnp.float32)
    w = jax.random.normal(jax.random.key(1), (k, h), jnp.float32)
    return x.astype(dtype), w.astype(dtype)


def _island(fn, mesh):
    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=(P(None, MODEL_AXIS), P(MODEL_AXIS, None)),
        out_specs=P(None, None), axis_names={MODEL_AXIS}, check_vma=False,
    )


def _mono_psum(x, w, mesh):
    return _island(lambda xl, wl: lax.psum(xl @ wl, MODEL_AXIS), mesh)(x, w)


def _per_tile_psum(x, w, mesh, tiles):
    def f(xl, wl):
        outs = [lax.psum(c, MODEL_AXIS)
                for c in jnp.split(xl @ wl, tiles, axis=0)]
        return jnp.concatenate(outs, axis=0)

    return _island(f, mesh)(x, w)


def _untiled_int8(x, w, mesh, block_size):
    return _island(
        lambda xl, wl: quantized_psum_tp(
            xl @ wl, MODEL_AXIS, block_size=block_size, tag="t_ref_q"
        ),
        mesh,
    )(x, w)


class TestTiledRingParity:
    @pytest.mark.slow
    @pytest.mark.parametrize("tiles", [1, 2, 4])
    def test_fp32_none_bitwise_vs_monolithic_psum(self, devices8, tiles):
        mesh = _mesh(devices8, 2)
        x, w = _operands(jnp.float32)
        ref = _mono_psum(x, w, mesh)
        out = tiled_tp_matmul(x, w, mesh, tiles, tag="t_tp_f32")
        assert out.dtype == jnp.float32
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    @pytest.mark.slow
    @pytest.mark.parametrize("tiles", [2, 4])
    def test_bf16_none_bitwise_vs_per_tile_psum(self, devices8, tiles):
        mesh = _mesh(devices8, 2)
        x, w = _operands(jnp.bfloat16)
        ref = _per_tile_psum(x, w, mesh, tiles)
        out = tiled_tp_matmul(x, w, mesh, tiles, tag="t_tp_bf16")
        assert out.dtype == jnp.bfloat16
        np.testing.assert_array_equal(
            np.asarray(out, np.float32), np.asarray(ref, np.float32)
        )
        # vs the FUSED monolithic baseline: 1-ulp convert-sinking artifact
        mono = np.asarray(_mono_psum(x, w, mesh), np.float32)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), mono,
            atol=float(np.max(np.abs(mono))) * 2.0 ** -7,
        )

    @pytest.mark.slow
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("tiles", [1, 2, 4])
    def test_int8_bitwise_vs_untiled_wire(self, devices8, dtype, tiles):
        mesh = _mesh(devices8, 2)
        x, w = _operands(dtype)
        ref = _untiled_int8(x, w, mesh, block_size=64)
        out = tiled_tp_matmul(x, w, mesh, tiles, comm_quant="int8",
                              block_size=64, tag="t_tp_q")
        assert out.dtype == dtype
        np.testing.assert_array_equal(
            np.asarray(out, np.float32), np.asarray(ref, np.float32)
        )

    @pytest.mark.slow
    def test_world4_fp32_and_int8_bitwise(self, devices8):
        mesh = _mesh(devices8, 4)
        x, w = _operands(jnp.float32)
        np.testing.assert_array_equal(
            np.asarray(tiled_tp_matmul(x, w, mesh, 4, tag="t_tp_w4")),
            np.asarray(_mono_psum(x, w, mesh)),
        )
        np.testing.assert_array_equal(
            np.asarray(tiled_tp_matmul(x, w, mesh, 4, comm_quant="int8",
                                       block_size=64, tag="t_tp_w4q")),
            np.asarray(_untiled_int8(x, w, mesh, block_size=64)),
        )

    @pytest.mark.slow
    def test_nondivisible_rows_fall_back_bitwise(self, devices8):
        mesh = _mesh(devices8, 2)
        x, w = _operands(jnp.float32, t=6)
        reset_wire_stats()
        out = tiled_tp_matmul(x, w, mesh, 4, tag="t_tp_fb")
        np.testing.assert_array_equal(
            np.asarray(out), np.asarray(_mono_psum(x, w, mesh))
        )
        assert wire_stats()["t_tp_fb"]["tiles"] == 1
        reset_wire_stats()

    def test_world_one_is_plain_matmul(self, devices8):
        mesh = _mesh(devices8, 1)
        x, w = _operands(jnp.float32)
        np.testing.assert_array_equal(
            np.asarray(tiled_tp_matmul(x, w, mesh, 4, tag="t_tp_w1")),
            np.asarray(x @ w),
        )


class TestWireStatsTiles:
    @pytest.mark.slow
    def test_tile_count_recorded_per_tag(self, devices8):
        mesh = _mesh(devices8, 2)
        x, w = _operands(jnp.float32)
        reset_wire_stats()
        tiled_tp_matmul(x, w, mesh, 4, tag="t_ws_tiles")
        stats = wire_stats()["t_ws_tiles"]
        assert stats["tiles"] == 4 and stats["sites"] >= 1
        reset_wire_stats()
        assert "t_ws_tiles" not in wire_stats()


# ---------------------------------------------------------------------------
# HLO structure: per-tile collectives are independent peers
# ---------------------------------------------------------------------------
def _permute_antichain(text: str):
    """(n_permutes, max antichain) over the collective-permutes of the
    lowered module: parse the func with the permutes into an SSA def-use
    graph, take reachability as the dependency order, and bucket permutes
    by longest-chain height — equal heights are pairwise unreachable, so
    the largest bucket is a lower bound on the max antichain."""
    chunks = re.split(r"(?=func\.func)", text)
    body = max(chunks, key=lambda c: c.count("collective_permute"))
    defs, order = {}, []
    for line in body.splitlines():
        m = re.match(r"\s*%(\w+)(?::\d+)?\s*=\s*(.*)", line)
        if not m:
            continue
        rid, rhs = m.group(1), m.group(2)
        ops = [t.split("#")[0] for t in re.findall(r"%(\w+(?:#\d+)?)", rhs)]
        defs[rid] = ("collective_permute" in rhs, ops)
        order.append(rid)

    anc = {}

    def ancestors(rid):
        if rid in anc:
            return anc[rid]
        anc[rid] = set()  # cycle guard for malformed text
        out = set()
        for o in defs.get(rid, (False, []))[1]:
            out.add(o)
            out |= ancestors(o)
        anc[rid] = out
        return out

    permutes = [r for r in order if defs[r][0]]
    height = {}
    for r in permutes:  # SSA order is topological
        deps = [p for p in permutes if p != r and p in ancestors(r)]
        height[r] = 1 + max((height[d] for d in deps), default=-1)
    widths = {}
    for h in height.values():
        widths[h] = widths.get(h, 0) + 1
    return len(permutes), max(widths.values()) if widths else 0


class TestHLOStructure:
    @pytest.mark.slow
    @pytest.mark.parametrize("comm_quant", ["none", "int8"])
    def test_per_tile_permutes_are_peers(self, devices8, comm_quant):
        """The acceptance criterion: >= tp_overlap_tiles collective ops with
        no serializing dependency chain between them."""
        tiles = 4
        mesh = _mesh(devices8, 2)
        x = jnp.zeros((16, 64), jnp.float32)
        w = jnp.zeros((64, 64), jnp.float32)
        fn = jax.jit(lambda a, b: tiled_tp_matmul(
            a, b, mesh, tiles, comm_quant=comm_quant, block_size=64,
            tag="t_hlo"))
        n, antichain = _permute_antichain(fn.lower(x, w).as_text())
        # none: one RS permute per tile at W=2; int8: payload+scale planes
        expected = tiles if comm_quant == "none" else 2 * tiles
        assert n >= expected, f"only {n} collective-permutes lowered"
        assert antichain >= tiles, (
            f"max antichain {antichain} < {tiles}: per-tile collectives "
            "are serialized, the overlap claim is void"
        )

    @pytest.mark.slow
    def test_engine_decode_program_has_tiled_peers(self, devices8):
        """Same assertion against a real serving program: the tp2 split-step
        lowering must hand XLA >= tp_overlap_tiles independent permutes."""
        from deepspeed_tpu.analysis import verify as dv
        from deepspeed_tpu.inference.config import RaggedInferenceEngineConfig
        from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
        from deepspeed_tpu.models import get_config, init_params

        reset_topology()
        try:
            set_topology(Topology(data=4, model=2, devices=devices8))
            cfg = get_config("tiny", n_layers=2, dtype="float32",
                             max_seq_len=512)
            params = init_params(cfg, jax.random.key(0))
            rc = RaggedInferenceEngineConfig.from_dict({
                "dtype": "float32", "tp_size": 2,
                "comm_overlap": "tiled", "tp_overlap_tiles": 4,
                "kv_cache": {"block_size": 16, "num_blocks": 64,
                             "max_blocks_per_seq": 8},
                "state_manager": {"max_ragged_batch_size": 64,
                                  "max_ragged_sequence_count": 4},
            })
            eng = InferenceEngineV2(cfg, params, rc)
            captured = {}
            dv._capture_builder(eng, "_build_split_step", captured, "split")
            eng.generate([np.arange(1, 9, dtype=np.int32)], max_new_tokens=2)
            fn, args = captured["split"]
            n, antichain = _permute_antichain(fn.lower(*args).as_text())
            assert antichain >= 4, (
                f"decode program max antichain {antichain} < 4"
            )
        finally:
            reset_topology()


# ---------------------------------------------------------------------------
# engine_v2 seam: tiled decode is bit-identical to the monolithic wire
# ---------------------------------------------------------------------------
class TestEngineTiledParity:
    def _run(self, devices8, prompts, sampling, **overrides):
        from deepspeed_tpu.inference.config import RaggedInferenceEngineConfig
        from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
        from deepspeed_tpu.models import get_config, init_params

        cfg = get_config("tiny", n_layers=2, dtype="float32", max_seq_len=512)
        params = init_params(cfg, jax.random.key(0))
        reset_topology()
        try:
            set_topology(Topology(data=4, model=2, devices=devices8))
            rc = RaggedInferenceEngineConfig.from_dict({
                "dtype": "float32", "tp_size": 2,
                "kv_cache": {"block_size": 16, "num_blocks": 64,
                             "max_blocks_per_seq": 8},
                "state_manager": {"max_ragged_batch_size": 64,
                                  "max_ragged_sequence_count": 4},
                **sampling, **overrides,
            })
            eng = InferenceEngineV2(cfg, params, rc)
            outs = eng.generate(prompts, max_new_tokens=5)
            return eng, [np.asarray(o) for o in outs]
        finally:
            reset_topology()

    @pytest.mark.slow
    @pytest.mark.parametrize("sampling", [
        {"greedy": True},
        {"greedy": False, "temperature": 1.0, "top_k": 8, "seed": 3},
    ], ids=["greedy", "seeded"])
    def test_tiled_decode_bit_identical_to_none(self, devices8, sampling):
        """The acceptance gate: comm_overlap='tiled' token streams must be
        BITWISE identical to comm_overlap='none' at tp2, greedy and seeded
        (the fp32 ring reduces in the same order psum does)."""
        prompts = [np.arange(1, 9), np.arange(21, 33), np.arange(5, 10)]
        _, ref = self._run(devices8, prompts, sampling)
        eng, out = self._run(devices8, prompts, sampling,
                             comm_overlap="tiled", tp_overlap_tiles=2)
        for o, r in zip(out, ref):
            np.testing.assert_array_equal(o, r)
        info = eng.comm_wire_info()
        assert info["comm_overlap"] == "tiled" and info["tp_tiled_active"]
        assert info["tp_overlap_tiles"] == 2
        wires = info["wires"]
        assert any(w.get("tiles", 1) > 1 for w in wires.values()), wires

    @pytest.mark.slow
    def test_tiled_int8_bit_identical_to_untiled_int8(self, devices8):
        prompts = [np.arange(1, 9), np.arange(21, 33)]
        _, ref = self._run(devices8, prompts, {"greedy": True},
                           comm_quant="int8")
        _, out = self._run(devices8, prompts, {"greedy": True},
                           comm_quant="int8", comm_overlap="tiled",
                           tp_overlap_tiles=2)
        for o, r in zip(out, ref):
            np.testing.assert_array_equal(o, r)

    def test_engine_rejects_comm_overlap_typo(self):
        from deepspeed_tpu.inference.config import RaggedInferenceEngineConfig
        from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
        from deepspeed_tpu.models import get_config, init_params

        cfg = get_config("tiny", n_layers=2, dtype="float32", max_seq_len=512)
        params = init_params(cfg, jax.random.key(0))
        rc = RaggedInferenceEngineConfig.from_dict({
            "dtype": "float32", "comm_overlap": "chunked",
            "kv_cache": {"block_size": 16, "num_blocks": 64,
                         "max_blocks_per_seq": 8},
            "state_manager": {"max_ragged_batch_size": 64,
                              "max_ragged_sequence_count": 4},
        })
        with pytest.raises(ValueError, match="comm_overlap"):
            InferenceEngineV2(cfg, params, rc)

    def test_engine_build_resets_wire_stats(self):
        from deepspeed_tpu.comm.quantized import record_wire
        from deepspeed_tpu.inference.config import RaggedInferenceEngineConfig
        from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
        from deepspeed_tpu.models import get_config, init_params

        record_wire("t_stale_tag", 100, 200, tiles=8)
        cfg = get_config("tiny", n_layers=2, dtype="float32", max_seq_len=512)
        params = init_params(cfg, jax.random.key(0))
        rc = RaggedInferenceEngineConfig.from_dict({
            "dtype": "float32",
            "kv_cache": {"block_size": 16, "num_blocks": 64,
                         "max_blocks_per_seq": 8},
            "state_manager": {"max_ragged_batch_size": 64,
                              "max_ragged_sequence_count": 4},
        })
        InferenceEngineV2(cfg, params, rc)
        # the stale tag from the previous configuration must be gone
        assert "t_stale_tag" not in wire_stats()


# ---------------------------------------------------------------------------
# ZeRO-3: tiled prefetch-bucket gathers
# ---------------------------------------------------------------------------
class TestBucketedGatherTiled:
    @pytest.fixture
    def mesh4(self, devices8):
        return Mesh(np.array(devices8[:4]), ("data",))

    def _gather(self, fn, mesh, leaves, dims, tiles, **kw):
        def local(*ls):
            return tuple(fn(list(ls), dims, "data", tiles=tiles, **kw))

        return jax.jit(jax.shard_map(
            local, mesh=mesh,
            in_specs=tuple(P("data") for _ in leaves),
            out_specs=tuple(P(None) for _ in leaves),
            axis_names={"data"}, check_vma=False,
        ))(*leaves)

    @pytest.mark.slow
    @pytest.mark.parametrize("tiles", [2, 3, 8])
    def test_plain_gather_tiled_bitwise(self, mesh4, tiles):
        from deepspeed_tpu.runtime.zero.overlap import bucketed_all_gather

        rng = np.random.RandomState(0)
        leaves = [jnp.asarray(rng.randn(*s).astype(np.float32))
                  for s in [(8, 16), (4, 5), (12, 7, 3)]]
        dims = [0, 0, 0]
        base = self._gather(bucketed_all_gather, mesh4, leaves, dims, 1)
        out = self._gather(bucketed_all_gather, mesh4, leaves, dims, tiles)
        for a, b in zip(base, out):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    @pytest.mark.slow
    @pytest.mark.parametrize("tiles", [2, 3, 8])
    def test_quantized_gather_tiled_bitwise(self, mesh4, tiles):
        from deepspeed_tpu.runtime.zero.overlap import (
            bucketed_quantized_all_gather,
        )

        rng = np.random.RandomState(1)
        leaves = [jnp.asarray(rng.randn(*s).astype(np.float32))
                  for s in [(8, 16), (4, 5), (12, 7, 3)]]
        dims = [0, 0, 0]
        base = self._gather(bucketed_quantized_all_gather, mesh4, leaves,
                            dims, 1, block_size=64)
        out = self._gather(bucketed_quantized_all_gather, mesh4, leaves,
                           dims, tiles, block_size=64)
        for a, b in zip(base, out):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestZero3TiledTrainParity:
    @pytest.mark.slow
    def test_train_losses_and_params_bitwise(self, devices8):
        """ZeRO-3 train steps with comm_overlap='tiled' must track the
        monolithic-gather run bit-for-bit: the tiled prefetch gathers are
        pure transport (the tile count includes 3, the uneven-split path)."""
        import deepspeed_tpu
        from tests.unit.simple_model import (
            batch_of,
            make_mlp_params,
            mlp_loss_fn,
            random_dataset,
        )

        n_steps = 3
        dataset = random_dataset(n=64 * n_steps)

        def run(comm_overlap):
            engine, _, _, _ = deepspeed_tpu.initialize(
                model=mlp_loss_fn,
                model_parameters=make_mlp_params(jax.random.key(0)),
                config={
                    "train_micro_batch_size_per_gpu": 8,
                    "optimizer": {"type": "AdamW", "params": {"lr": 1e-2}},
                    "zero_optimization": {"stage": 3,
                                          "param_persistence_threshold": 0},
                    "comm_overlap": comm_overlap,
                    "tp_overlap_tiles": 3,
                    "mesh": {"data": 8},
                    "steps_per_print": 10**9,
                },
            )
            losses, pos = [], 0
            for _ in range(n_steps):
                losses.append(float(engine.train_batch(
                    batch=batch_of(dataset, pos, 64))))
                pos += 64
            return engine, losses

        ref_eng, losses_ref = run("none")
        tiled_eng, losses_t = run("tiled")
        assert losses_t == losses_ref  # bitwise: exact float equality
        ref_leaves = jax.tree_util.tree_leaves(ref_eng.params)
        t_leaves = jax.tree_util.tree_leaves(tiled_eng.params)
        for a, b in zip(ref_leaves, t_leaves):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_config_rejects_typo(self):
        from deepspeed_tpu.runtime.config import ConfigError, DeepSpeedConfig

        with pytest.raises(ConfigError, match="comm_overlap"):
            DeepSpeedConfig.load({
                "train_micro_batch_size_per_gpu": 1,
                "comm_overlap": "chunked",
            })
        with pytest.raises(ConfigError, match="tp_overlap_tiles"):
            DeepSpeedConfig.load({
                "train_micro_batch_size_per_gpu": 1,
                "comm_overlap": "tiled",
                "tp_overlap_tiles": 0,
            })
