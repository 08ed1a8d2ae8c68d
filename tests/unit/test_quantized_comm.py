"""Quantized collectives (comm/quantized.py): int8-inside-the-collective
parity/bounded-error vs the fp32 collectives, trace-time wire-byte
accounting, the comm_quant config seam, and the three hot wires behind it
(serving TP decode, MoE EP exchange, pipeline activation sends).

Error bounds are analytic, not tuned: symmetric int8 block quantization has
per-element error ≤ block_absmax / (2·127) ≤ max|x| / 254 per hop, and a
W-way reduce sums W independently-quantized terms (+ one re-quantized
gather hop for the psum), so every assert below uses that worst case.

The heavyweight parity tests (multi-second shard_map/engine compiles) are
marked ``slow`` to stay out of the tier-1 wall-clock budget; the
quantized-comm gate in tools/run_smoke.sh runs this file without the marker
filter, so every commit still exercises them."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.comm.quantized import (
    check_comm_quant,
    quantized_all_gather,
    quantized_all_to_all,
    quantized_ppermute,
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


@pytest.fixture
def topo8(devices8):
    reset_topology()
    t = Topology(data=8, devices=devices8)
    set_topology(t)
    yield t
    reset_topology()


def _hop_bound(x, hops=1):
    """Worst-case per-element int8 blockwise error over ``hops`` quantize
    hops of data bounded by max|x| (scale ≤ absmax/127, round ≤ scale/2)."""
    return hops * float(np.max(np.abs(np.asarray(x, np.float64)))) / 254.0


class TestCheckCommQuant:
    def test_valid_modes(self):
        assert check_comm_quant("none") == "none"
        assert check_comm_quant("int8") == "int8"
        assert check_comm_quant(None) == "none"  # unset config field

    @pytest.mark.parametrize("bad", ["int4", "INT8", "fp8", "yes"])
    def test_typo_raises(self, bad):
        with pytest.raises(ValueError, match="comm_quant"):
            check_comm_quant(bad)


class TestQuantizedPsumTP:
    @pytest.mark.slow
    def test_matches_fp32_psum_nondivisible_chunk(self, topo8):
        # local size 100: not a block multiple AND chunk 100/8 not whole —
        # exercises the pad-to-W*block path
        x = jax.random.normal(jax.random.key(0), (8, 100), jnp.float32)

        def f(v):
            q = quantized_psum_tp(v[0], "data", tag="t_psum_a")
            r = jax.lax.psum(v[0], "data")
            return q[None], r[None]

        q, r = jax.shard_map(f, mesh=topo8.mesh, in_specs=P("data", None),
                         out_specs=P("data", None), check_vma=False)(x)
        # RS hop sums 8 quantized terms; AG hop re-quantizes the 8x-larger sum
        bound = 8 * _hop_bound(x) + _hop_bound(np.asarray(r[0]))
        assert np.max(np.abs(np.asarray(q[0]) - np.asarray(r[0]))) <= bound
        assert q.dtype == x.dtype

    @pytest.mark.slow
    def test_bf16_input(self, topo8):
        x = jax.random.normal(jax.random.key(1), (8, 256)).astype(jnp.bfloat16)

        def f(v):
            q = quantized_psum_tp(v[0], "data", tag="t_psum_b")
            r = jax.lax.psum(v[0].astype(jnp.float32), "data")
            return q[None], r[None]

        q, r = jax.shard_map(f, mesh=topo8.mesh, in_specs=P("data", None),
                         out_specs=P("data", None), check_vma=False)(x)
        assert q.dtype == jnp.bfloat16
        # quant bound + bf16's own 2^-8 relative rounding of the result
        bound = 8 * _hop_bound(np.float32(x)) + _hop_bound(np.asarray(r[0])) \
            + np.max(np.abs(np.asarray(r[0]))) * 2.0 ** -8
        assert np.max(np.abs(np.asarray(q[0], np.float32) - np.asarray(r[0]))) <= bound

    def test_one_rank_axis_is_bitexact_identity(self, topo8):
        # MODEL_AXIS has size 1 under Topology(data=8): the seam must be a
        # no-op, not a quantize round-trip
        x = jax.random.normal(jax.random.key(2), (4, 37), jnp.float32)
        out = jax.shard_map(
            lambda v: quantized_psum_tp(v, MODEL_AXIS, tag="t_psum_c"),
            mesh=topo8.mesh, in_specs=P(), out_specs=P(), check_vma=False,
        )(x)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(x))


class TestQuantizedAllToAll:
    @pytest.mark.slow
    def test_matches_raw_all_to_all(self, topo8):
        # row size 35: not a block multiple (pad path)
        x = jax.random.normal(jax.random.key(3), (8, 8, 5, 7), jnp.float32)

        def f(v):
            q = quantized_all_to_all(v[0], "data", split_dim=0, concat_dim=0,
                                     tag="t_a2a_a")
            r = jax.lax.all_to_all(v[0], "data", split_axis=0, concat_axis=0,
                                   tiled=True)
            return q[None], r[None]

        q, r = jax.shard_map(f, mesh=topo8.mesh, in_specs=P("data", None, None, None),
                         out_specs=P("data", None, None, None), check_vma=False)(x)
        assert np.max(np.abs(np.asarray(q) - np.asarray(r))) <= _hop_bound(x)

    @pytest.mark.slow
    def test_reduce_matches_summed_shards(self, topo8):
        # reduce=True = the reference all_to_all_quant_reduce (qgZ RS): rank
        # r's slice of sum_w x_w. Concatenated over ranks that is the sum of
        # the 8 rank blocks of the global array.
        X = jax.random.normal(jax.random.key(4), (64, 33), jnp.float32)

        def f(v):
            return quantized_all_to_all(v, "data", split_dim=0, reduce=True,
                                        tag="t_a2a_b")

        out = jax.shard_map(f, mesh=topo8.mesh, in_specs=P("data", None),
                        out_specs=P("data", None), check_vma=False)(X)
        expected = np.asarray(X).reshape(8, 8, 33).sum(axis=0)
        assert np.max(np.abs(np.asarray(out) - expected)) <= 8 * _hop_bound(X)

    def test_nondivisible_split_dim_raises(self, topo8):
        X = jnp.ones((8, 6, 4))  # local split_dim = 6, W = 8

        with pytest.raises(ValueError, match="divisible"):
            jax.shard_map(
                lambda v: quantized_all_to_all(v[0], "data", tag="t_a2a_c")[None],
                mesh=topo8.mesh, in_specs=P("data", None, None),
                out_specs=P("data", None, None), check_vma=False,
            )(X)


class TestQuantizedAllGather:
    @pytest.mark.slow
    def test_matches_raw_all_gather(self, topo8):
        x = jax.random.normal(jax.random.key(5), (24, 5), jnp.float32)

        def f(v):
            q = quantized_all_gather(v, "data", dim=0, tag="t_ag_a")
            r = jax.lax.all_gather(v, "data", axis=0, tiled=True)
            return q, r

        q, r = jax.shard_map(f, mesh=topo8.mesh, in_specs=P("data", None),
                         out_specs=P(None, None), check_vma=False)(x)
        assert q.shape == r.shape == (24, 5)
        assert np.max(np.abs(np.asarray(q) - np.asarray(r))) <= _hop_bound(x)


class TestQuantizedPpermute:
    @pytest.mark.slow
    def test_tree_send_with_raw_small_leaves(self, topo8):
        perm = [(i, i + 1) for i in range(7)]  # rank 0 receives nothing
        tree = {
            "act": jax.random.normal(jax.random.key(6), (8, 2, 600), jnp.float32),
            "aux": jnp.arange(8, dtype=jnp.float32),  # scalar per rank
        }

        def f(act, aux):
            out = quantized_ppermute(
                {"act": act[0], "aux": aux[0]}, "data", perm, tag="t_pp_a"
            )
            ref = jax.tree.map(
                lambda l: jax.lax.ppermute(l, "data", perm=perm),
                {"act": act[0], "aux": aux[0]},
            )
            return out["act"][None], out["aux"][None], ref["act"][None], ref["aux"][None]

        q_act, q_aux, r_act, r_aux = jax.shard_map(
            f, mesh=topo8.mesh,
            in_specs=(P("data", None, None), P("data")),
            out_specs=(P("data", None, None), P("data"),
                       P("data", None, None), P("data")),
            check_vma=False,
        )(tree["act"], tree["aux"])
        # big leaf: quantized, bounded error; zeros-for-unsourced preserved
        assert np.max(np.abs(np.asarray(q_act) - np.asarray(r_act))) <= _hop_bound(tree["act"])
        np.testing.assert_array_equal(np.asarray(q_act[0]), np.zeros((2, 600)))
        # small leaf rides the raw ppermute: bit-exact
        np.testing.assert_array_equal(np.asarray(q_aux), np.asarray(r_aux))


class TestWireStats:
    @pytest.mark.slow
    def test_reduction_ratio_recorded_per_tag(self, topo8):
        # local rows of 2048 = W*block_size so the RS hop's pad-to-W·block
        # rounding doesn't dominate (at serving sizes the pad is noise; a
        # 512-element toy row would honestly report reduction < 1)
        reset_wire_stats()
        xb = jax.random.normal(jax.random.key(7), (8, 2048)).astype(jnp.bfloat16)
        xf = jax.random.normal(jax.random.key(8), (8, 2048), jnp.float32)

        def f(b, f32):
            return (
                quantized_psum_tp(b[0], "data", tag="t_ws_bf16")[None],
                quantized_psum_tp(f32[0], "data", tag="t_ws_fp32")[None],
            )

        jax.shard_map(f, mesh=topo8.mesh,
                  in_specs=(P("data", None), P("data", None)),
                  out_specs=(P("data", None), P("data", None)),
                  check_vma=False)(xb, xf)
        stats = wire_stats()
        bf = stats["t_ws_bf16"]
        fp = stats["t_ws_fp32"]
        assert bf["sites"] >= 1 and fp["sites"] >= 1
        # the multichip A/B gate's number: ≥1.8x off bf16, ~2x that off fp32
        assert bf["reduction"] >= 1.8
        assert fp["reduction"] >= 3.5
        reset_wire_stats()

    def test_small_ppermute_leaf_records_parity_bytes(self, topo8):
        reset_wire_stats()
        perm = [(i, (i + 1) % 8) for i in range(8)]
        jax.shard_map(
            lambda v: quantized_ppermute(v, "data", perm, tag="t_ws_small"),
            mesh=topo8.mesh, in_specs=P("data"), out_specs=P("data"),
            check_vma=False,
        )(jnp.arange(8, dtype=jnp.float32))
        w = wire_stats()["t_ws_small"]
        # raw passthrough: quant bytes == fp bytes, reduction exactly 1
        assert w["wire_bytes_int8"] == w["wire_bytes_fp"]
        assert w["reduction"] == 1.0
        reset_wire_stats()


class TestBlockQuantEdgeCases:
    """Satellite coverage for the underlying block-quant collectives the
    quantized layer builds on (ops/quantizer/block_quant.py)."""

    @pytest.mark.slow
    def test_all_gather_along_nondivisible_block(self, topo8):
        from deepspeed_tpu.ops.quantizer import block_quant as bq

        x = jax.random.normal(jax.random.key(9), (8, 3, 11), jnp.float32)

        def f(v):
            q = bq.quantized_all_gather_along(v, "data", dim=0, block_size=256)
            r = jax.lax.all_gather(v, "data", axis=0, tiled=True)
            return q, r

        q, r = jax.shard_map(f, mesh=topo8.mesh, in_specs=P("data", None, None),
                         out_specs=P(None, None, None), check_vma=False)(x)
        assert q.shape == (8, 3, 11)
        assert np.max(np.abs(np.asarray(q) - np.asarray(r))) <= _hop_bound(x)

    @pytest.mark.slow
    def test_reduce_scatter_along_bf16(self, topo8):
        from deepspeed_tpu.ops.quantizer import block_quant as bq

        x = jax.random.normal(jax.random.key(10), (8, 16, 9)).astype(jnp.bfloat16)

        def f(v):
            q = bq.quantized_reduce_scatter_along(v[0], "data", dim=0, mean=True)
            r = jax.lax.psum(v[0].astype(jnp.float32), "data") / 8.0
            i = jax.lax.axis_index("data")
            r_slice = jax.lax.dynamic_slice_in_dim(r, i * 2, 2, axis=0)
            return q[None], r_slice[None]

        q, r = jax.shard_map(f, mesh=topo8.mesh, in_specs=P("data", None, None),
                         out_specs=(P("data", None, None), P("data", None, None)),
                         check_vma=False)(x)
        assert q.dtype == jnp.bfloat16
        # mean of 8 quantized terms /8 + bf16 rounding of the output
        bound = _hop_bound(np.float32(x)) + np.max(np.abs(np.asarray(r))) * 2.0 ** -8
        assert np.max(np.abs(np.asarray(q, np.float32) - np.asarray(r))) <= bound

    def test_reduce_scatter_along_nondivisible_raises(self, topo8):
        from deepspeed_tpu.ops.quantizer import block_quant as bq

        with pytest.raises(ValueError, match="divisible"):
            jax.shard_map(
                lambda v: bq.quantized_reduce_scatter_along(v[0], "data", dim=0)[None],
                mesh=topo8.mesh, in_specs=P("data", None),
                out_specs=P("data", None), check_vma=False,
            )(jnp.ones((8, 6)))

    @pytest.mark.slow
    def test_loco_allreduce_error_feedback(self, topo8):
        from deepspeed_tpu.ops.quantizer import block_quant as bq

        x = jax.random.normal(jax.random.key(11), (8, 300), jnp.float32)
        err0 = jnp.zeros((300,), jnp.bfloat16)

        def f(v, e):
            out, new_err = bq.loco_quantized_allreduce(v[0], e[0], "data")
            r = jax.lax.pmean(v[0], "data")
            return out[None], new_err[None], r[None]

        out, new_err, r = jax.shard_map(
            f, mesh=topo8.mesh,
            in_specs=(P("data", None), P("data", None)),
            out_specs=(P("data", None), P("data", None), P("data", None)),
            check_vma=False,
        )(x, jnp.broadcast_to(err0, (8, 300)))
        # mean of 8 quantized terms /8 + re-quantized gather hop
        bound = _hop_bound(x) + _hop_bound(np.asarray(r[0]))
        assert np.max(np.abs(np.asarray(out[0]) - np.asarray(r[0]))) <= bound
        # error buffer carries this step's residual: same shape/dtype,
        # finite, and non-zero (quantization is lossy on random data)
        assert new_err.dtype == err0.dtype and new_err.shape == (8, 300)
        ne = np.asarray(new_err[0], np.float32)
        assert np.isfinite(ne).all() and np.abs(ne).max() > 0


class TestMoEQuantWire:
    def _moe_setup(self, devices8, expert=4, **cfg_kw):
        from deepspeed_tpu.models import get_config, init_params

        reset_topology()
        set_topology(Topology(data=8 // expert, expert=expert, devices=devices8))
        cfg = get_config("mixtral-tiny", dtype="float32", **cfg_kw)
        params = init_params(cfg, jax.random.key(0))
        lp = jax.tree.map(lambda a: a[0], params["layers"])
        return cfg, lp

    @pytest.mark.slow
    def test_moe_quant_parity_with_gspmd_path(self, devices8):
        from deepspeed_tpu.models import get_config
        from deepspeed_tpu.parallel.moe import moe_mlp

        cfg_q, lp = self._moe_setup(devices8, comm_quant="int8")
        cfg_n = get_config("mixtral-tiny", dtype="float32")
        x = jax.random.normal(jax.random.key(1), (2, 16, cfg_q.hidden_size),
                              jnp.float32)
        try:
            out_q, aux_q, _ = moe_mlp(cfg_q, lp, x)
            out_n, aux_n, _ = moe_mlp(cfg_n, lp, x)
        finally:
            reset_topology()
        # gating is identical (it runs outside the island), so aux matches
        np.testing.assert_allclose(float(aux_q), float(aux_n), rtol=1e-6)
        scale = float(np.max(np.abs(np.asarray(out_n)))) + 1e-6
        err = float(np.max(np.abs(np.asarray(out_q) - np.asarray(out_n))))
        assert err <= 0.05 * scale, f"moe quant err {err} vs scale {scale}"

    def test_moe_quant_nondivisible_experts_raises(self, devices8):
        from deepspeed_tpu.parallel.moe import moe_mlp

        # expert axis 8 does not divide mixtral-tiny's 4 experts
        cfg, lp = self._moe_setup(devices8, expert=8, comm_quant="int8")
        x = jnp.ones((2, 16, cfg.hidden_size), jnp.float32)
        try:
            with pytest.raises(ValueError, match="divisible"):
                moe_mlp(cfg, lp, x)
        finally:
            reset_topology()

    def test_quantized_ep_active_seam(self, devices8):
        from deepspeed_tpu.models import get_config
        from deepspeed_tpu.parallel.moe.mappings import quantized_ep_active

        reset_topology()
        try:
            set_topology(Topology(data=2, expert=4, devices=devices8))
            assert quantized_ep_active(get_config("mixtral-tiny", comm_quant="int8"))
            assert not quantized_ep_active(get_config("mixtral-tiny"))
            set_topology(Topology(data=8, devices=devices8))  # expert axis 1
            assert not quantized_ep_active(get_config("mixtral-tiny", comm_quant="int8"))
        finally:
            reset_topology()


class TestPipelineQuantWire:
    @pytest.mark.slow
    def test_gpipe_loss_close_and_grads_finite(self, devices8):
        from deepspeed_tpu.models import TransformerConfig, init_params
        from deepspeed_tpu.runtime.pipe import make_pipelined_loss_fn

        reset_topology()
        topo = Topology(pipe=4, data=2)
        set_topology(topo)
        try:
            cfg = TransformerConfig(
                vocab_size=128, hidden_size=64, n_layers=4, n_heads=4,
                max_seq_len=64, dtype="float32",
            )
            params = init_params(cfg, jax.random.key(0))
            toks = np.random.default_rng(0).integers(
                0, 128, size=(8, 33)).astype(np.int32)
            batch = {"input_ids": toks}
            ref_fn = make_pipelined_loss_fn(cfg, micro_batches=4, topo=topo)
            q_fn = make_pipelined_loss_fn(cfg, micro_batches=4, topo=topo,
                                          comm_quant="int8")
            loss_ref = float(jax.jit(ref_fn)(params, batch))
            loss_q, grads_q = jax.jit(jax.value_and_grad(q_fn))(params, batch)
            np.testing.assert_allclose(float(loss_q), loss_ref, rtol=0.05)
            for g in jax.tree_util.tree_leaves(grads_q):
                assert np.isfinite(np.asarray(g)).all()
        finally:
            reset_topology()

    @pytest.mark.slow
    def test_1f1b_loss_close_to_unquantized(self, devices8):
        from deepspeed_tpu.models import TransformerConfig, init_params
        from deepspeed_tpu.runtime.pipe import make_1f1b_loss_fn

        reset_topology()
        topo = Topology(pipe=4, data=2)
        set_topology(topo)
        try:
            cfg = TransformerConfig(
                vocab_size=128, hidden_size=64, n_layers=4, n_heads=4,
                max_seq_len=64, dtype="float32",
            )
            params = init_params(cfg, jax.random.key(0))
            toks = np.random.default_rng(1).integers(
                0, 128, size=(8, 33)).astype(np.int32)
            batch = {"input_ids": toks}
            ref = make_1f1b_loss_fn(cfg, micro_batches=4, topo=topo)
            quant = make_1f1b_loss_fn(cfg, micro_batches=4, topo=topo,
                                      comm_quant="int8")
            loss_ref, _ = jax.jit(ref.custom_value_and_grad)(params, batch)
            loss_q, grads_q = jax.jit(quant.custom_value_and_grad)(params, batch)
            np.testing.assert_allclose(float(loss_q), float(loss_ref), rtol=0.05)
            for g in jax.tree_util.tree_leaves(grads_q):
                assert np.isfinite(np.asarray(g)).all()
        finally:
            reset_topology()

    def test_bad_comm_quant_rejected(self, devices8):
        from deepspeed_tpu.models import TransformerConfig
        from deepspeed_tpu.runtime.pipe import make_pipelined_loss_fn

        reset_topology()
        topo = Topology(pipe=2, data=4)
        set_topology(topo)
        try:
            cfg = TransformerConfig(
                vocab_size=64, hidden_size=32, n_layers=2, n_heads=4,
                max_seq_len=32, dtype="float32",
            )
            with pytest.raises(ValueError, match="comm_quant"):
                make_pipelined_loss_fn(cfg, micro_batches=2, topo=topo,
                                       comm_quant="int4")
        finally:
            reset_topology()


class TestServingTPQuantWire:
    @pytest.mark.slow
    def test_tp_decode_greedy_agreement(self, devices8):
        """The acceptance gate: TP decode with comm_quant='int8' must agree
        with the full-width run up to quantization noise. On a random-init
        model greedy margins are knife-edge (top-2 logit gaps of ~1e-2 on a
        ~10-wide logit spread), so bit-parity of every token is not the
        right oracle; the gate is: every quantized-run token is an argmax of
        the fp32 teacher-forced logits to within a small fraction of the
        logit spread, and most tokens match the fp32 run exactly. A trained
        model's margins dwarf the quantization noise, which is what makes
        greedy outputs bit-stable in production."""
        from deepspeed_tpu.inference.config import RaggedInferenceEngineConfig
        from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
        from deepspeed_tpu.models import forward, get_config, init_params

        cfg = get_config("tiny", n_layers=2, dtype="float32", max_seq_len=512)
        params = init_params(cfg, jax.random.key(0))
        prompts = [np.arange(1, 9), np.arange(21, 33), np.arange(5, 10)]

        def run(comm_quant):
            reset_topology()
            try:
                set_topology(Topology(data=4, model=2, devices=devices8))
                rc = RaggedInferenceEngineConfig.from_dict({
                    "dtype": "float32", "tp_size": 2, "comm_quant": comm_quant,
                    "kv_cache": {"block_size": 16, "num_blocks": 64,
                                 "max_blocks_per_seq": 8},
                    "state_manager": {"max_ragged_batch_size": 64,
                                      "max_ragged_sequence_count": 4},
                })
                eng = InferenceEngineV2(cfg, params, rc)
                return eng, eng.generate(prompts, max_new_tokens=5)
            finally:
                reset_topology()

        _, outs_ref = run("none")
        eng_q, outs_q = run("int8")

        fwd = jax.jit(forward, static_argnames=("config",))
        exact = total = 0
        for prompt, o_q, o_ref in zip(prompts, outs_q, outs_ref):
            np.testing.assert_array_equal(o_q[: len(prompt)], prompt)
            assert len(o_q) == len(o_ref)
            exact += int(np.sum(o_q[len(prompt):] == o_ref[len(prompt):]))
            total += len(o_q) - len(prompt)
            # teacher-force the quantized trajectory through the dense fp32
            # model: each chosen token must be argmax-within-noise
            logits = np.asarray(fwd(params, jnp.asarray(o_q[None, :-1]), cfg)[0])
            for t in range(len(prompt) - 1, len(o_q) - 1):
                row = logits[0, t]
                spread = float(row.max() - row.min())
                gap = float(row.max() - row[o_q[t + 1]])
                assert gap <= 0.05 * spread, (
                    f"token {o_q[t + 1]} at pos {t + 1}: logit gap {gap} "
                    f"exceeds quant noise ({0.05 * spread})"
                )
        assert exact >= 0.5 * total, f"only {exact}/{total} tokens match fp32 run"
        info = eng_q.comm_wire_info()
        assert info["comm_quant"] == "int8" and info["tp_quant_active"]
        wires = info["wires"]
        assert any(t.startswith("tp_") for t in wires), wires

    def test_comm_quant_inactive_at_tp1(self, devices8):
        from deepspeed_tpu.inference.config import RaggedInferenceEngineConfig
        from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
        from deepspeed_tpu.models import get_config, init_params

        cfg = get_config("tiny", n_layers=2, dtype="float32", max_seq_len=512)
        params = init_params(cfg, jax.random.key(0))
        rc = RaggedInferenceEngineConfig.from_dict({
            "dtype": "float32", "comm_quant": "int8",
            "kv_cache": {"block_size": 16, "num_blocks": 64,
                         "max_blocks_per_seq": 8},
            "state_manager": {"max_ragged_batch_size": 64,
                              "max_ragged_sequence_count": 4},
        })
        eng = InferenceEngineV2(cfg, params, rc)
        info = eng.comm_wire_info()
        # validated but inert: no model axis to quantize over
        assert info["comm_quant"] == "int8" and not info["tp_quant_active"]

    def test_engine_rejects_comm_quant_typo(self):
        from deepspeed_tpu.inference.config import RaggedInferenceEngineConfig
        from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
        from deepspeed_tpu.models import get_config, init_params

        cfg = get_config("tiny", n_layers=2, dtype="float32", max_seq_len=512)
        params = init_params(cfg, jax.random.key(0))
        rc = RaggedInferenceEngineConfig.from_dict({
            "dtype": "float32", "comm_quant": "int4",
            "kv_cache": {"block_size": 16, "num_blocks": 64,
                         "max_blocks_per_seq": 8},
            "state_manager": {"max_ragged_batch_size": 64,
                              "max_ragged_sequence_count": 4},
        })
        with pytest.raises(ValueError, match="comm_quant"):
            InferenceEngineV2(cfg, params, rc)


class TestServingMetricsCommWire:
    def test_metrics_render_per_wire_gauges(self):
        from deepspeed_tpu.serving.metrics import ServingMetrics

        m = ServingMetrics()
        m.update_comm_quant({
            "comm_quant": "int8", "tp_quant_active": True,
            "wires": {"tp_psum": {"sites": 2, "wire_bytes_int8": 1040,
                                  "wire_bytes_fp": 4096, "reduction": 3.94}},
        })
        snap = m.snapshot()
        assert snap["comm_quant_int8"] == 1
        assert snap["comm_wire_tp_psum_reduction"] == pytest.approx(3.94)
        text = m.prometheus_text()
        assert 'dstpu_serving_comm_wire_reduction{wire="tp_psum"} 3.94' in text
        assert 'dstpu_serving_comm_wire_bytes_quant{wire="tp_psum"} 1040' in text

    def test_metrics_default_off(self):
        from deepspeed_tpu.serving.metrics import ServingMetrics

        m = ServingMetrics()
        assert m.snapshot()["comm_quant_int8"] == 0
