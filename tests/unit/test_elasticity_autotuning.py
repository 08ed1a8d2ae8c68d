"""Elasticity + autotuning tests (analogue of reference tests/unit/elasticity
+ tests/unit/autotuning)."""

import numpy as np
import pytest

from deepspeed_tpu.autotuning import (
    Autotuner,
    AutotunerConfig,
    ModelInfo,
    activation_memory_per_chip,
    zero_memory_per_chip,
)
from deepspeed_tpu.elasticity import (
    ElasticityConfigError,
    ElasticityError,
    compute_elastic_config,
    elastic_resume_plan,
    get_valid_gpus,
    micro_batch_for_world,
)

BASE_CONFIG = {
    "elasticity": {
        "enabled": True,
        "max_train_batch_size": 10000,
        "micro_batch_sizes": [8, 12, 16, 17],
        "min_gpus": 32,
        "max_gpus": 1500,
        "min_time": 20,
        "version": 0.1,
    }
}


class TestElasticity:
    def test_candidate_selection(self):
        """The reference's own doc example: these knobs give a highly
        composite batch size with many valid worlds."""
        batch, valid = compute_elastic_config(BASE_CONFIG)
        assert batch <= 10000
        # every valid count decomposes the batch through some micro batch
        for g in valid[:20]:
            assert any(batch % (mb * g) == 0 for mb in [8, 12, 16, 17])
        assert len(valid) > 20  # elasticity means MANY valid counts

    def test_world_size_validation(self):
        batch, valid = compute_elastic_config(BASE_CONFIG)
        ok = valid[len(valid) // 2]
        compute_elastic_config(BASE_CONFIG, world_size=ok)  # no raise
        bad = max(valid) + 1
        if bad not in valid:
            with pytest.raises(ElasticityError):
                compute_elastic_config(BASE_CONFIG, world_size=bad)

    def test_return_microbatch(self):
        batch, valid, micro = compute_elastic_config(
            BASE_CONFIG, world_size=valid_world(BASE_CONFIG), return_microbatch=True
        )
        w = valid_world(BASE_CONFIG)
        assert batch % (micro * w) == 0

    def test_missing_section_raises(self):
        with pytest.raises(ElasticityConfigError):
            compute_elastic_config({})

    def test_disabled_raises(self):
        cfg = {"elasticity": dict(BASE_CONFIG["elasticity"], enabled=False)}
        with pytest.raises(ElasticityConfigError):
            compute_elastic_config(cfg)

    def test_get_valid_gpus(self):
        assert get_valid_gpus(96, [8, 12], 1, 12) == [1, 2, 3, 4, 6, 8, 12]

    def test_micro_batch_for_world_prefers_larger(self):
        assert micro_batch_for_world(96, [2, 4, 8], 4) == 8
        with pytest.raises(ElasticityError):
            micro_batch_for_world(97, [2, 4, 8], 4)

    def test_resume_plan_preserves_global_batch(self):
        w = valid_world(BASE_CONFIG)
        plan = elastic_resume_plan(BASE_CONFIG, w)
        assert (
            plan["train_micro_batch_size_per_gpu"]
            * plan["gradient_accumulation_steps"]
            * w
            == plan["train_batch_size"]
        )
        # scale down to another valid count: same global batch size
        batch, valid = compute_elastic_config(BASE_CONFIG)
        other = [g for g in valid if g != w][0]
        plan2 = elastic_resume_plan(BASE_CONFIG, other)
        assert plan2["train_batch_size"] == plan["train_batch_size"]


def valid_world(cfg):
    _, valid = compute_elastic_config(cfg)
    return valid[len(valid) // 2]


class TestElasticityV02:
    CFG = {
        "elasticity": {
            "enabled": True,
            "max_train_batch_size": 512,
            "micro_batch_sizes": [2, 4],
            "min_gpus": 1,
            "max_gpus": 64,
            "version": 0.2,
            "num_gpus_per_node": 4,
            "model_parallel_size": 2,
        }
    }

    def test_every_advertised_world_decomposes(self):
        batch, valid = compute_elastic_config(self.CFG, world_size=4)
        for dp in valid:
            assert any(batch % (mb * dp) == 0 for mb in [2, 4]), (batch, dp)

    def test_mp_aware_resume_plan(self):
        # 8 chips, mp=2 → dp world 4: realized samples/step must equal batch
        plan = elastic_resume_plan(self.CFG, 8)
        dp = 8 // 2
        assert (
            plan["train_micro_batch_size_per_gpu"]
            * plan["gradient_accumulation_steps"]
            * dp
            == plan["train_batch_size"]
        )


def test_autotuner_latency_minimizes():
    from deepspeed_tpu.autotuning import Autotuner, AutotunerConfig, ModelInfo

    def runner(exp):  # latency: smaller micro = smaller latency
        return float(exp["micro_batch"])

    tuner = Autotuner(
        ModelInfo(50_000_000, 512, 8, 1024), 16 * 2**30, dp_world=8, runner=runner,
        config=AutotunerConfig(fast=False, metric="latency", max_experiments=100),
    )
    best, val = tuner.tune()
    assert best["micro_batch"] == 1  # lowest latency wins, not highest value


class TestAutotuner:
    MI = ModelInfo(num_params=700_000_000, hidden_size=1536, num_layers=20, seq_len=2048)
    HBM = 16 * 2**30

    def test_memory_model_monotonic(self):
        # higher stages shard more state
        mems = [zero_memory_per_chip(10**9, s, dp_world=8) for s in range(4)]
        assert mems == sorted(mems, reverse=True)
        # remat reduces activation memory
        assert activation_memory_per_chip(8, 2048, 1024, 16, remat=True) < \
            activation_memory_per_chip(8, 2048, 1024, 16, remat=False)

    def test_feasibility_pruning(self):
        tuner = Autotuner(self.MI, self.HBM, dp_world=1, runner=lambda e: 1.0)
        # stage 0 with 700M params needs 12.6GB of state: huge micros infeasible
        assert not tuner.memory_feasible(0, 32, remat=True)
        assert tuner.memory_feasible(3, 4, remat=True) == tuner.memory_feasible(0, 4, remat=True)

    def test_grid_search_finds_synthetic_optimum(self):
        # synthetic cost: throughput peaks at stage 1, micro 8
        def runner(exp):
            return 100 - 10 * abs(exp["zero_stage"] - 1) - abs(exp["micro_batch"] - 8)

        tuner = Autotuner(
            ModelInfo(50_000_000, 512, 8, 1024), self.HBM, dp_world=8, runner=runner,
            config=AutotunerConfig(fast=False, tuner_type="gridsearch", max_experiments=100),
        )
        best, val = tuner.tune()
        assert best["zero_stage"] == 1 and best["micro_batch"] == 8

    def test_fast_mode_early_stops(self):
        calls = []

        def runner(exp):
            calls.append(exp)
            return float(exp["micro_batch"])  # bigger micro always better

        tuner = Autotuner(
            ModelInfo(50_000_000, 512, 8, 1024), self.HBM, dp_world=8, runner=runner,
            config=AutotunerConfig(fast=True),
        )
        best, val = tuner.tune()
        assert best is not None
        # fast mode: largest feasible micro first, then stop on regression —
        # far fewer experiments than the full grid
        assert len(calls) < 24

    def test_failed_experiments_are_records_not_crashes(self):
        def runner(exp):
            if exp["micro_batch"] > 2:
                raise MemoryError("RESOURCE_EXHAUSTED")
            return 1.0

        tuner = Autotuner(
            ModelInfo(50_000_000, 512, 8, 1024), self.HBM, dp_world=8, runner=runner,
            config=AutotunerConfig(fast=False, max_experiments=10),
        )
        best, val = tuner.tune()
        assert best is not None and best["micro_batch"] <= 2
        assert any(r.metric_val is None for r in tuner.records)
        assert "FAIL" in tuner.summary()


class TestExtendedAutotuner:
    """Round-4 space (VERDICT r3 #8): remat policy / flash block / shape
    candidates, cost-model ordering, and real subprocess experiments."""

    HBM = 16_000_000_000

    def _tuner(self, runner, **cfg_kw):
        from deepspeed_tpu.autotuning import Autotuner, AutotunerConfig, ModelInfo

        cfg = AutotunerConfig(
            fast=True,
            max_experiments=cfg_kw.pop("max_experiments", 50),
            stages=(3,),
            micro_batch_sizes=(2, 4, 8),
            remat_policies=("nothing", "flash"),
            flash_blocks=(256, 512),
            shapes=(
                {"hidden_size": 2304, "n_layers": 10, "n_heads": 18,
                 "n_kv_heads": 6, "ffn_hidden_size": 6912, "vocab_size": 32000,
                 "max_seq_len": 2048},
                {"hidden_size": 1536, "n_layers": 20, "n_heads": 12,
                 "n_kv_heads": 6, "ffn_hidden_size": 4096, "vocab_size": 32000,
                 "max_seq_len": 2048},
            ),
            **cfg_kw,
        )
        return Autotuner(
            ModelInfo(767_000_000, 2304, 10, 2048), self.HBM, dp_world=1,
            runner=runner, config=cfg,
        )

    def test_space_covers_new_knobs_and_is_cost_ordered(self):
        from deepspeed_tpu.autotuning import predicted_score

        tuner = self._tuner(lambda e: 1.0)
        space = tuner._space()
        assert space, "extended space empty"
        keys = set(space[0])
        assert {"remat_policy", "flash_block", "shape"} <= keys
        scores = [predicted_score(e) for e in space]
        assert scores == sorted(scores, reverse=True), "space not cost-ordered"
        # both shapes and both policies survive the memory prune
        assert {e["shape"]["hidden_size"] for e in space} == {2304, 1536}
        assert {e["remat_policy"] for e in space} == {"nothing", "flash"}

    def test_matmul_precision_in_space_and_cost_model(self):
        """The round-4 +4.3pp lever: int8 must be enumerable, ranked ahead of
        bf16 by the cost model at equal other knobs, and findable."""
        from deepspeed_tpu.autotuning import predicted_score

        tuner = self._tuner(
            lambda e: 50.0 + (4.3 if e.get("matmul_precision") == "int8" else 0.0),
            matmul_precisions=("default", "int8"),
        )
        space = tuner._space()
        precs = {e.get("matmul_precision", "default") for e in space}
        assert precs == {"default", "int8"}
        base = {"zero_stage": 3, "micro_batch": 6, "remat_policy": "flash", "flash_block": 512}
        assert predicted_score({**base, "matmul_precision": "int8"}) > predicted_score(base)
        best, val = tuner.tune()
        assert best.get("matmul_precision") == "int8"

    def test_exp_runner_honors_matmul_precision(self):
        """The subprocess runner threads matmul_precision into the config —
        a CPU smoke run with int8 must execute and report ok."""
        from deepspeed_tpu.autotuning.exp_runner import run

        from deepspeed_tpu.parallel.topology import reset_topology

        reset_topology()
        out = run({
            "shape": {"vocab_size": 256, "hidden_size": 64, "n_layers": 2,
                      "n_heads": 4, "max_seq_len": 128, "dtype": "float32"},
            "zero_stage": 0, "micro_batch": 8, "remat_policy": "nothing",
            "matmul_precision": "int8", "seq": 64, "steps": 1, "warmup": 1,
            "platform": "cpu",
        })
        reset_topology()
        assert out["ok"], out

    def test_finds_the_hand_swept_bench_config(self):
        """An oracle runner encoding the round-3 measurements (h=2304 GQA +
        remat nothing/flash at micro 6-8 measured best) must lead the tuner
        to that config — the search that round 3 did by hand."""

        def oracle(exp):
            s = exp["shape"]
            mfu = 40.0
            mfu += 10.0 if s["hidden_size"] == 2304 else 0.0
            mfu += {"nothing": 3.0, "flash": 2.5}.get(exp["remat_policy"], 0)
            mfu += {8: 2.0, 4: 1.0, 2: 0.0}[exp["micro_batch"]]
            return mfu

        tuner = self._tuner(oracle)
        best, val = tuner.tune()
        # the oracle's argmax over the FEASIBLE space (micro 8 at h=2304 is
        # memory-pruned at stage-3 dp=1, exactly like the real chip where the
        # bench tops out at micro 6) must be what the tuner returns
        want = max(tuner._space(), key=oracle)
        assert val == oracle(want), (best, want)
        assert best["shape"]["hidden_size"] == 2304
        assert best["remat_policy"] == "nothing"
        # cost-model ordering should find it in the first few experiments
        assert len(tuner.records) <= 8, len(tuner.records)

    def test_estimate_params_close_to_real_count(self):
        from deepspeed_tpu.autotuning import estimate_params
        from deepspeed_tpu.models import get_config, init_params, num_params

        import jax

        cfg = get_config("bench-767m")
        shape = {"hidden_size": 2304, "n_layers": 10, "n_heads": 18,
                 "n_kv_heads": 6, "ffn_hidden_size": 6912, "vocab_size": 32000,
                 "max_seq_len": 2048}
        est = estimate_params(shape)
        real = num_params(init_params(cfg, jax.random.key(0)))
        assert abs(est - real) / real < 0.02, (est, real)

    def test_subprocess_runner_end_to_end(self):
        """One REAL subprocess experiment (reference launcher round trip):
        isolated python process builds the engine, times steps, reports."""
        from deepspeed_tpu.autotuning import SubprocessRunner

        runner = SubprocessRunner(metric="tok_s", platform="cpu", steps=1, warmup=1,
                                  timeout_s=240, verbose=False)
        val = runner({
            "zero_stage": 0,
            "micro_batch": 2,
            "remat_policy": "dots_with_no_batch_dims",
            "shape": {"vocab_size": 256, "hidden_size": 64, "n_layers": 2,
                      "n_heads": 4, "max_seq_len": 128, "dtype": "float32"},
            "seq": 64,
        })
        assert val is not None and val > 0

    def test_subprocess_runner_maps_crash_to_none(self):
        from deepspeed_tpu.autotuning import SubprocessRunner

        runner = SubprocessRunner(metric="tok_s", platform="cpu", timeout_s=240,
                                  verbose=False)
        val = runner({"zero_stage": 0, "micro_batch": 1,
                      "shape": {"hidden_size": -1}})  # invalid shape → failure
        assert val is None


@pytest.mark.slow  # ~47s: the heaviest single tier-1 test; the subprocess
# scheduler path stays tier-1 via TestExtendedAutotuner (end_to_end + crash)
def test_tune_serving_cpu_smoke():
    """The serving tuner runs isolated experiments and returns a best config
    (tiny shape on CPU; VERDICT r4 next-step #8 — v2 knobs against the
    serving metric through the same subprocess scheduler)."""
    from deepspeed_tpu.autotuning.autotuner import tune_serving

    tiny = dict(vocab_size=128, hidden_size=64, n_layers=2, n_heads=4,
                n_kv_heads=2, max_seq_len=256, dtype="float32")
    common = dict(shape=tiny, concurrency=4, max_new=8, repeats=1,
                  block_size=16, num_blocks=64, max_blocks_per_seq=8,
                  token_budget=128, prompt_min=8, prompt_max=32)
    space = [
        {"prompt_chunk": 64, "max_prompt_chunks": 2, **common},
        {"prompt_chunk": 32, "max_prompt_chunks": 4, **common},
    ]
    best, val, records = tune_serving(
        max_experiments=2, timeout_s=600, platform="cpu", space=space,
    )
    assert len(records) == 2
    assert best is not None and val is not None and val > 0
    assert best["prompt_chunk"] in (64, 32)
