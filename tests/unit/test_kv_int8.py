"""Int8 paged KV cache: pool byte accounting (capacity ~doubles at a fixed
HBM budget), engine-level generate parity across payload dtypes and attention
impls (kernel runs interpreted on CPU), spec-decode invariance, and the
serving-stack wiring (driver admission capacity, health, /metrics gauges).
"""

import numpy as np
import pytest

import jax

from deepspeed_tpu.inference.v2.kv_pool import (
    blocks_for_budget,
    bytes_per_block,
    capacity_multiplier,
)

# ---------------------------------------------------------------------------
# pool byte accounting
# ---------------------------------------------------------------------------
class TestPoolAccounting:
    def test_capacity_multiplier_head_dim_128(self):
        """At head_dim=128 the int8 pool (1-byte payload + 4-byte fp32 scale
        per head vector) fits >= 1.9x the blocks of a bf16 pool in the same
        byte budget: ratio = 2d/(d+4) = 256/132 ~ 1.94."""
        mult = capacity_multiplier(16, 2, 128, "int8")
        assert mult >= 1.9, mult
        per_bf16 = bytes_per_block(16, 2, 128, 2, "bf16")
        per_int8 = bytes_per_block(16, 2, 128, 2, "int8")
        assert per_bf16 / per_int8 == pytest.approx(mult)
        # exact byte math: 2 pools * L * (payload + scale plane)
        vecs = 16 * 2  # block_size * kv_heads
        assert per_bf16 == 2 * 2 * vecs * 128 * 2
        assert per_int8 == 2 * 2 * (vecs * 128 * 1 + vecs * 4)

    def test_blocks_for_budget_doubles(self):
        """The driver-facing form of the capacity claim: a byte budget that
        admits N bf16 blocks admits >= 1.9*N int8 blocks (both reserve the
        +1 trash block inside the budget)."""
        per = bytes_per_block(16, 2, 128, 2, "bf16")
        budget = (512 + 1) * per
        n_bf16 = blocks_for_budget(budget, 16, 2, 128, 2, "bf16")
        n_int8 = blocks_for_budget(budget, 16, 2, 128, 2, "int8")
        assert n_bf16 == 512
        assert n_int8 >= 1.9 * n_bf16, (n_bf16, n_int8)

    def test_budget_too_small_raises(self):
        with pytest.raises(ValueError):
            blocks_for_budget(1, 16, 2, 128, 2, "bf16")

    def test_unknown_dtype_raises(self):
        with pytest.raises(ValueError):
            bytes_per_block(16, 2, 128, 2, "fp8")


# ---------------------------------------------------------------------------
# engine: generate parity across payload dtype and attention impl
# ---------------------------------------------------------------------------
def _make_engine(kv_dtype="bf16", impl="auto", spec_k=0, num_blocks=64, seed=0):
    from deepspeed_tpu.inference.config import RaggedInferenceEngineConfig
    from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.models import TransformerConfig, init_params

    # head_dim = 128/2 = 64: a kernel-tileable head dim, so impl="kernel"
    # exercises the same program TPU would run (interpreted on CPU)
    mc = TransformerConfig(
        vocab_size=128, hidden_size=128, n_layers=2, n_heads=2, n_kv_heads=1,
        max_seq_len=256, dtype="float32",
    )
    params = init_params(mc, jax.random.key(seed))
    rc = RaggedInferenceEngineConfig.from_dict({
        "dtype": "float32", "spec_k": spec_k,
        "paged_attention_impl": impl,
        "kv_cache": {"block_size": 16, "num_blocks": num_blocks,
                     "max_blocks_per_seq": 8, "kv_cache_dtype": kv_dtype},
        "state_manager": {"max_tracked_sequences": 16,
                          "max_ragged_batch_size": 256,
                          "max_ragged_sequence_count": 4, "max_context": 256},
    })
    return InferenceEngineV2(mc, params, rc), mc


def _prompts(n=3, seed=0, vocab=128):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab, size=(12,)).astype(np.int32)
            for _ in range(n)]


class TestEngineInt8:
    def test_int8_stream_matches_bf16_on_tiny_model(self):
        """On this tiny float32 model the argmax stream survives int8 KV
        quantization unchanged — the end-to-end 'quality holds' check (the
        numeric error bound lives in tests/unit/ops/test_paged_attention)."""
        eng_a, _ = _make_engine(kv_dtype="bf16")
        out_a = eng_a.generate(_prompts(), max_new_tokens=6)
        eng_b, _ = _make_engine(kv_dtype="int8")
        out_b = eng_b.generate(_prompts(), max_new_tokens=6)
        for a, b in zip(out_a, out_b):
            np.testing.assert_array_equal(a, b)
        assert eng_b.kv_cache_dtype == "int8"
        assert eng_a.kv_cache_dtype == "bf16"

    # bf16 leg rides the unfiltered run_smoke gate: tier-1's 870 s budget is
    # tight, and the int8 leg compiles the same kernel programs plus dequant
    @pytest.mark.parametrize(
        "kv_dtype",
        [pytest.param("bf16", marks=pytest.mark.slow), "int8"],
    )
    def test_kernel_impl_matches_dense(self, kv_dtype):
        """Decode through the Pallas kernel (interpret mode on CPU) streams
        the same tokens as the dense XLA gather, for both payload dtypes."""
        eng_d, _ = _make_engine(kv_dtype=kv_dtype, impl="dense")
        out_d = eng_d.generate(_prompts(seed=1), max_new_tokens=6)
        eng_k, _ = _make_engine(kv_dtype=kv_dtype, impl="kernel")
        assert eng_k.paged_attention_impl == "kernel"
        out_k = eng_k.generate(_prompts(seed=1), max_new_tokens=6)
        for a, b in zip(out_d, out_k):
            np.testing.assert_array_equal(a, b)

    def test_auto_resolves_dense_off_tpu(self):
        eng, _ = _make_engine(impl="auto")
        assert eng.paged_attention_impl == "dense"

    def test_kv_pool_info_reports_dtype_and_bytes(self):
        eng, mc = _make_engine(kv_dtype="int8", num_blocks=64)
        info = eng.kv_pool_info()
        assert info["kv_cache_dtype"] == "int8"
        assert info["kv_capacity_multiplier"] == pytest.approx(
            capacity_multiplier(16, mc.kv_heads, mc.head_dim, "int8")
        )
        per = bytes_per_block(16, mc.kv_heads, mc.head_dim, mc.n_layers, "int8")
        assert info["kv_pool_bytes"] == (64 + 1) * per
        assert info["kv_bytes_per_block"] == per

    def test_bad_kv_dtype_raises(self):
        with pytest.raises(ValueError):
            _make_engine(kv_dtype="fp8")

    def test_bad_impl_raises(self):
        with pytest.raises(ValueError, match="paged_attention_impl"):
            _make_engine(impl="fused")


class TestSpecInt8:
    # run_smoke's int8 gate runs this unfiltered; tier-1 skips it (slow) to
    # stay inside the 870 s budget — the verify-step kernel+int8 program is
    # still lowered in tier-1 via the donation-verifier int8 pass
    @pytest.mark.slow
    def test_spec_round_invariant_with_int8_kernel(self):
        """Speculative decoding is a latency knob, not a numerics knob: with
        the int8 pool AND the kernel impl, spec-on serving streams the same
        tokens as spec-off on the identical engine config."""
        from deepspeed_tpu.serving.driver import ServingDriver
        from deepspeed_tpu.serving.request import SamplingParams

        def run(spec_k):
            eng, _ = _make_engine(kv_dtype="int8", impl="kernel",
                                  spec_k=spec_k, num_blocks=128)
            driver = ServingDriver(eng).start()
            reqs = [driver.submit(p, SamplingParams(max_new_tokens=16,
                                                    ignore_eos=True))
                    for p in _prompts(seed=2)]
            for r in reqs:
                assert r.wait(300)
            health = driver.health()
            driver.shutdown()
            return [list(r.generated) for r in reqs], health

        off, _ = run(0)
        on, health = run(4)
        assert off == on, "spec-on int8 stream differs from spec-off"
        assert health["spec"]["rounds"] > 0
        assert health["kv_cache_dtype"] == "int8"


# ---------------------------------------------------------------------------
# serving wiring: admission capacity, health, metrics
# ---------------------------------------------------------------------------
class TestServingInt8:
    def test_fixed_budget_doubles_driver_admission_capacity(self):
        """Size both pools from the SAME byte budget (the `--kv-pool-bytes`
        path) and check the driver's admission limit — total KV blocks —
        roughly doubles under int8, and that health/metrics report it."""
        from deepspeed_tpu.serving.driver import ServingDriver

        totals = {}
        for kv_dtype in ("bf16", "int8"):
            # budget sized so head_dim=64 engines stay tiny: 64 bf16 blocks
            per = bytes_per_block(16, 1, 64, 2, "bf16")
            budget = (64 + 1) * per
            nb = blocks_for_budget(budget, 16, 1, 64, 2, kv_dtype)
            eng, _ = _make_engine(kv_dtype=kv_dtype, num_blocks=nb)
            driver = ServingDriver(eng)
            totals[kv_dtype] = driver._kv_total
            health = driver.health()
            assert health["kv_cache_dtype"] == kv_dtype
            assert health["kv_total_blocks"] == nb
            assert health["kv_pool_bytes"] <= budget
            text = driver.metrics.prometheus_text()
            flag = 1 if kv_dtype == "int8" else 0
            assert f"dstpu_serving_kv_cache_int8 {flag}" in text
            assert "dstpu_serving_kv_pool_bytes" in text
            assert "dstpu_serving_kv_capacity_multiplier" in text
        # head_dim=64: 2d/(d+4) ~ 1.88x — the >=1.9 bar needs d=128 and is
        # pinned by TestPoolAccounting; here assert the driver SEES ~2x
        assert totals["int8"] >= 1.8 * totals["bf16"], totals

    def test_serve_cli_flags_parse(self):
        from deepspeed_tpu.inference.cli import serve_parse_args

        args = serve_parse_args([
            "--model", "/tmp/nope", "--kv-cache-dtype", "int8",
            "--kv-pool-bytes", str(1 << 20), "--paged-attention-impl", "dense",
        ])
        assert args.kv_cache_dtype == "int8"
        assert args.kv_pool_bytes == 1 << 20
        assert args.paged_attention_impl == "dense"
        with pytest.raises(SystemExit):
            serve_parse_args(["--model", "x", "--kv-cache-dtype", "fp8"])
