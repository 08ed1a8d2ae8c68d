"""Splash-style scheduled block-sparse attention (interpret mode on CPU).

Covers the full pipeline: mask predicates → compacted block schedules →
the scalar-prefetch kernel → the ``attention(impl="splash")`` seam → the
model config → serving chunked prefill. The pruning claims are asserted
structurally: grid size and counted block visits scale with the number of
ACTIVE blocks, never with nq*nk.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.attention import attention, mha_reference
from deepspeed_tpu.ops.sparse_attention import (
    BigBirdSparsityConfig,
    BSLongformerSparsityConfig,
    CausalMask,
    DocumentMask,
    FixedSparsityConfig,
    FullMask,
    LocalMask,
    MultiHeadMask,
    SparseSelfAttention,
    schedule_from_layout,
    schedule_from_mask,
    sparse_attention,
    sparse_attention_reference,
    splash_attention,
)
from deepspeed_tpu.ops.sparse_attention.mask import EMPTY, FULL, PARTIAL, LayoutMask

BLOCK = 64


def _qkv(b=1, h=2, s=256, d=64, seed=0, h_kv=None):
    kq, kk, kv = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(kq, (b, h, s, d)),
            jax.random.normal(kk, (b, h_kv or h, s, d)),
            jax.random.normal(kv, (b, h_kv or h, s, d)))


def _status_oracle(mask, bq, bk):
    """Blockwise status recomputed from the dense token mask — the slow
    ground truth every analytic ``block_status`` must match."""
    tm = mask.token_mask()
    sq, sk = tm.shape
    nq, nk = sq // bq, sk // bk
    blocks = tm.reshape(nq, bq, nk, bk).transpose(0, 2, 1, 3)
    any_ = blocks.any(axis=(2, 3))
    all_ = blocks.all(axis=(2, 3))
    return np.where(all_, FULL, np.where(any_, PARTIAL, EMPTY))


class TestMasks:
    @pytest.mark.parametrize("mask", [
        FullMask((256, 256)),
        CausalMask((256, 256)),
        LocalMask((256, 256), 96),
        LocalMask((256, 256), 64),  # window == block edge
        DocumentMask([0] * 100 + [1] * 60 + [2] * 96),
        DocumentMask([0, 1] * 128),  # non-monotone ids: blockwise-exact path
        LocalMask((256, 256), 80) & CausalMask((256, 256)),
        LayoutMask(np.eye(4, dtype=np.int32), 64),
    ])
    @pytest.mark.parametrize("bq,bk", [(64, 64), (32, 64)])
    def test_block_status_matches_token_oracle(self, mask, bq, bk):
        np.testing.assert_array_equal(mask.block_status(bq, bk),
                                      _status_oracle(mask, bq, bk))

    def test_multi_head_mask_stacks(self):
        # per-head LAYOUTS may vary; causal/window predicates must agree
        # (the kernel compiles one predicate set)
        heads = [LayoutMask(np.eye(4, dtype=np.int32), 64),
                 LayoutMask(np.ones((4, 4), np.int32), 64)]
        mh = MultiHeadMask(heads)
        st = mh.block_status(64, 64)
        assert st.shape == (2, 4, 4)
        for i, m in enumerate(heads):
            np.testing.assert_array_equal(st[i], m.block_status(64, 64))
        with pytest.raises(ValueError, match="predicate"):
            MultiHeadMask([CausalMask((256, 256)), LocalMask((256, 256), 96)])

    def test_and_empty_dominates_full_requires_both(self):
        both = LocalMask((256, 256), 96) & CausalMask((256, 256))
        st = both.block_status(64, 64)
        loc = LocalMask((256, 256), 96).block_status(64, 64)
        cau = CausalMask((256, 256)).block_status(64, 64)
        assert ((st == EMPTY) >= ((loc == EMPTY) | (cau == EMPTY))).all()
        assert ((st == FULL) <= ((loc == FULL) & (cau == FULL))).all()


class TestSchedule:
    def test_compaction_indices_and_kinds(self):
        mask = CausalMask((256, 256))
        sched = schedule_from_mask(mask, 64)
        st = mask.block_status(64, 64)
        # row i of a causal grid: blocks 0..i-1 FULL, block i PARTIAL
        for i in range(4):
            active = np.nonzero(st[i])[0]
            np.testing.assert_array_equal(sched.kv_index[0, i, :len(active)], active)
            np.testing.assert_array_equal(sched.step_kind[0, i, :len(active)],
                                          st[i, active])
            # padding repeats the LAST active index (Pallas copy elision)
            assert (sched.kv_index[0, i, len(active):] == active[-1]).all()
            assert (sched.step_kind[0, i, len(active):] == EMPTY).all()

    def test_grid_scales_with_active_blocks_not_nq_nk(self):
        """THE pruning invariant: the kernel grid covers grid_width steps per
        q row — the densest row's ACTIVE count — never the full nk."""
        s, w = 1024, 128
        dense_nk = s // BLOCK
        sched = schedule_from_mask(LocalMask((s, s), w), BLOCK)
        # a 128-window over 64-blocks touches at most 3 blocks per row
        assert sched.grid_width <= 3 < dense_nk
        assert sched.num_active <= 3 * sched.nq
        # widening the window widens the grid; the mapping is monotone
        wider = schedule_from_mask(LocalMask((s, s), 4 * w), BLOCK)
        assert sched.grid_width < wider.grid_width < dense_nk

    def test_block_visit_speedup_at_low_density(self):
        """Acceptance: >=2x fewer block visits than dense at <=0.35 density
        (CPU interpret proxy — counted visits, the TPU wall-clock analogue)."""
        s = 2048
        sched = schedule_from_mask(LocalMask((s, s), 256), BLOCK)
        dense_visits = sched.nq * sched.nk
        assert sched.density <= 0.35
        assert dense_visits / sched.num_active >= 2.0
        # the fwd grid itself (nq * grid_width) shrinks proportionally
        assert sched.nq * sched.grid_width <= 0.35 * dense_visits

    def test_degenerate_rows(self):
        # all-dense row + all-masked row in one layout
        layout = np.zeros((1, 4, 4), np.int32)
        layout[0, 0] = 1          # row 0 attends everything
        # row 2 attends nothing (dead row)
        layout[0, 1, 0] = layout[0, 3, 3] = 1
        sched = schedule_from_layout(layout, 64)
        assert sched.grid_width == 4          # densest row bounds the grid
        assert (sched.step_kind[0, 2] == EMPTY).all()

    def test_transposed_schedule_consistency(self):
        """q_index/step_kind_t (the dkv grid) lists exactly the transpose of
        the forward active set."""
        sched = schedule_from_mask(LocalMask((512, 512), 160), 64)
        fwd = set()
        for i in range(sched.nq):
            for j in range(sched.grid_width):
                if sched.step_kind[0, i, j] != EMPTY:
                    fwd.add((i, int(sched.kv_index[0, i, j])))
        bwd = set()
        for kk in range(sched.nk):
            for j in range(sched.grid_width_t):
                if sched.step_kind_t[0, kk, j] != EMPTY:
                    bwd.add((int(sched.q_index[0, kk, j]), kk))
        assert fwd == bwd

    def test_sparsity_config_make_schedule_matches_layout(self):
        cfg = BigBirdSparsityConfig(num_heads=2, block=BLOCK, num_random_blocks=1,
                                    num_sliding_window_blocks=3)
        layout = cfg.make_layout(512)
        sched = cfg.make_schedule(512)
        ref = schedule_from_layout(layout, BLOCK)
        np.testing.assert_array_equal(sched.kv_index, ref.kv_index)
        np.testing.assert_array_equal(sched.step_kind, ref.step_kind)


def _splash_vs_ref(q, k, v, sched, ref, rtol=2e-4, atol=2e-4, **kw):
    out = splash_attention(q, k, v, sched, interpret=True, **kw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=rtol, atol=atol)

    def loss_splash(q, k, v):
        return jnp.sum(jnp.square(splash_attention(q, k, v, sched,
                                                   interpret=True, **kw)))

    gs = jax.grad(loss_splash, argnums=(0, 1, 2))(q, k, v)
    return out, gs


class TestSplashKernel:
    def test_causal_fwd_bwd(self):
        q, k, v = _qkv(s=256)
        sched = schedule_from_mask(CausalMask((256, 256)), BLOCK)
        ref = mha_reference(q, k, v, causal=True)
        _, gs = _splash_vs_ref(q, k, v, sched, ref)
        gr = jax.grad(lambda q, k, v: jnp.sum(jnp.square(
            mha_reference(q, k, v, causal=True))), argnums=(0, 1, 2))(q, k, v)
        for a, b_, n in zip(gs, gr, "qkv"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       rtol=5e-4, atol=5e-4, err_msg=f"d{n}")

    def test_local_window_fwd_bwd(self):
        q, k, v = _qkv(s=512)
        w = 160
        sched = schedule_from_mask(LocalMask((512, 512), w), BLOCK)
        ref = mha_reference(q, k, v, causal=True, window=w)
        _, gs = _splash_vs_ref(q, k, v, sched, ref)
        gr = jax.grad(lambda q, k, v: jnp.sum(jnp.square(
            mha_reference(q, k, v, causal=True, window=w))),
            argnums=(0, 1, 2))(q, k, v)
        for a, b_, n in zip(gs, gr, "qkv"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       rtol=5e-4, atol=5e-4, err_msg=f"d{n}")

    def test_document_mask_static_segments(self):
        q, k, v = _qkv(s=256)
        ids = [0] * 100 + [1] * 60 + [2] * 96
        sched = schedule_from_mask(DocumentMask(ids) & CausalMask((256, 256)), BLOCK)
        seg = jnp.asarray(ids, jnp.int32)[None]
        ref = mha_reference(q, k, v, causal=True, segment_ids=seg)
        _splash_vs_ref(q, k, v, sched, ref)

    def test_traced_segment_ids(self):
        """seg_mode='all': traced packing ids mask every active step — the
        schedule stays causal-only (built without the ids)."""
        q, k, v = _qkv(s=256)
        ids = jnp.asarray([0] * 128 + [1] * 128, jnp.int32)[None]
        sched = schedule_from_mask(CausalMask((256, 256)), BLOCK)
        ref = mha_reference(q, k, v, causal=True, segment_ids=ids)
        _splash_vs_ref(q, k, v, sched, ref, segment_ids=ids)

    def test_gqa_heads_native(self):
        q, k, v = _qkv(h=4, h_kv=2, s=256, seed=1)
        sched = schedule_from_mask(LocalMask((256, 256), 96), BLOCK)
        ref = mha_reference(q, k, v, causal=True, window=96)
        out = splash_attention(q, k, v, sched, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)
        # GQA dk/dv: per-q-head grads group-reduce onto the shared kv head
        gk = jax.grad(lambda k: jnp.sum(jnp.square(
            splash_attention(q, k, v, sched, interpret=True))))(k)
        gkr = jax.grad(lambda k: jnp.sum(jnp.square(
            mha_reference(q, k, v, causal=True, window=96))))(k)
        np.testing.assert_allclose(np.asarray(gk), np.asarray(gkr),
                                   rtol=5e-4, atol=5e-4)

    @pytest.mark.parametrize("cfg_cls,kw", [
        (BigBirdSparsityConfig, {"num_random_blocks": 1,
                                 "num_sliding_window_blocks": 3,
                                 "different_layout_per_head": True}),
        (BSLongformerSparsityConfig, {"num_sliding_window_blocks": 3,
                                      "global_block_indices": (0,)}),
    ])
    def test_per_head_layouts_match_oracle_kernel(self, cfg_cls, kw):
        """BigBird/Longformer layouts through the schedule builder parity
        against the retained layout-predicate oracle kernel, fwd and bwd."""
        q, k, v = _qkv(h=4, s=256, seed=2)
        cfg = cfg_cls(num_heads=4, block=BLOCK, **kw)
        layout = cfg.make_layout(256)
        sched = cfg.make_schedule(256)
        ref = sparse_attention_reference(q, k, v, layout, BLOCK)
        _, gs = _splash_vs_ref(q, k, v, sched, ref)
        gr = jax.grad(lambda q, k, v: jnp.sum(jnp.square(
            sparse_attention_reference(q, k, v, layout, BLOCK))),
            argnums=(0, 1, 2))(q, k, v)
        for a, b_, n in zip(gs, gr, "qkv"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       rtol=5e-4, atol=5e-4, err_msg=f"d{n}")

    def test_dead_rows_zero_output_finite_grads(self):
        q, k, v = _qkv(h=1, s=256)
        layout = np.zeros((1, 4, 4), np.int32)
        layout[0, 0, 0] = 1
        layout[0, 3, :] = 1  # rows 1,2 dead
        sched = schedule_from_layout(layout, 64)
        out = splash_attention(q, k, v, sched, interpret=True)
        np.testing.assert_array_equal(np.asarray(out[:, :, 64:192]), 0.0)
        g = jax.grad(lambda q: jnp.sum(jnp.square(
            splash_attention(q, k, v, sched, interpret=True))))(q)
        assert np.isfinite(np.asarray(g)).all()

    def test_bias_raises_loudly_in_both_kernels(self):
        """Satellite: the historic bias signature drift — both entries must
        reject a dense bias instead of silently diverging."""
        q, k, v = _qkv(s=128)
        layout = np.ones((2, 2, 2), np.int32)
        bias = jnp.zeros((1, 1, 128, 128))
        with pytest.raises(NotImplementedError):
            sparse_attention(q, k, v, layout, 64, bias=bias, interpret=True)
        with pytest.raises(NotImplementedError):
            sparse_attention_reference(q, k, v, layout, 64, bias=bias)


class TestAttentionSeam:
    def test_impl_splash_derived_schedule(self):
        q, k, v = _qkv(s=256, seed=4)
        out = attention(q, k, v, causal=True, window=96, impl="splash")
        ref = mha_reference(q, k, v, causal=True, window=96)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)

    def test_auto_promotes_on_schedule(self):
        q, k, v = _qkv(s=256, seed=4)
        sched = schedule_from_mask(LocalMask((256, 256), 96), BLOCK)
        out = attention(q, k, v, causal=True, schedule=sched)
        ref = mha_reference(q, k, v, causal=True, window=96)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)

    def test_splash_rejects_bias_and_traced_flag(self):
        q, k, v = _qkv(s=128, seed=4)
        with pytest.raises(ValueError):
            attention(q, k, v, causal=True, impl="splash",
                      bias=jnp.zeros((1, 1, 128, 128)))
        with pytest.raises(ValueError):
            attention(q, k, v, causal=True, window=64, impl="splash",
                      window_flag=jnp.int32(1))


class TestModelAndServing:
    def test_transformer_splash_matches_dense(self):
        from deepspeed_tpu.models import forward, get_config, init_params

        cfg = get_config("tiny", dtype="float32", max_seq_len=256)
        params = init_params(cfg, jax.random.key(0))
        tok = jnp.asarray(np.arange(256)[None] % 97)
        ld, _ = forward(params, tok, cfg)
        for over in ({"attention_impl": "splash"},
                     {"attention_impl": "splash", "sliding_window": 96},
                     {"attention_impl": "splash",
                      "attn_sparsity": ("fixed", (("block", 64),
                                                  ("num_local_blocks", 4),
                                                  ("attention", "unidirectional")))}):
            c2 = dataclasses.replace(cfg, **over)
            ls, _ = forward(params, tok, c2)
            if not over.get("sliding_window") and "attn_sparsity" not in over:
                np.testing.assert_allclose(np.asarray(ls), np.asarray(ld),
                                           rtol=2e-4, atol=2e-4)
            else:  # different mask → different logits, but finite and shaped
                assert np.isfinite(np.asarray(ls)).all()

    def test_config_validation(self):
        from deepspeed_tpu.models import get_config

        with pytest.raises(ValueError, match="attn_sparsity"):
            get_config("tiny", attn_sparsity=("nope",))
        with pytest.raises(ValueError, match="alibi"):
            get_config("tiny", attention_impl="splash", position="alibi")
        with pytest.raises(ValueError, match="attn_layer_pattern"):
            get_config("tiny", attention_impl="splash", sliding_window=8,
                       attn_layer_pattern=(1,) * 2)


class TestSelfAttentionModule:
    def test_splash_path_matches_oracle(self):
        q, k, v = _qkv(h=2, s=256)
        cfg = BSLongformerSparsityConfig(num_heads=2, block=BLOCK,
                                         num_sliding_window_blocks=3)
        out = SparseSelfAttention(cfg, interpret=True)(q, k, v)
        ref = SparseSelfAttention(cfg, interpret=True, use_splash=False)(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.slow
class TestLongContext:
    def test_8k_local_window_parity(self):
        s, w = 8192, 512
        q, k, v = _qkv(b=1, h=1, s=s, d=64, seed=5)
        sched = schedule_from_mask(LocalMask((s, s), w), 512)
        assert sched.density < 0.15  # provable pruning at scale
        out = splash_attention(q, k, v, sched, interpret=True)
        ref = mha_reference(q, k, v, causal=True, window=w)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=5e-4, atol=5e-4)
