"""Unit tests of the benchmark's own arithmetic. Run by hand on the CPU:
``JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q``. Not part of the
repo's tier-1 suite (which collects ``tests/`` only)."""

import importlib
import json
import os
import re

import numpy as np
import pytest

from benchmarks.harness import flops, loadgen, stats, xplane
from benchmarks.harness.common import BENCH, REPO, Catalog

MIX = {"arrivals": {"law": "poisson", "rate": 5.0},
       "prompt_len": {"law": "lognormal", "median": 1536, "sigma": 0.6, "min": 512, "max": 3840},
       "output_len": {"law": "uniform", "min": 16, "max": 64}}


def same_requests(x, y):
    return all(np.array_equal(p.prompt, q.prompt) and p.max_new == q.max_new
               for (_, p), (_, q) in zip(x, y))


def due(sched):
    return [t for t, _ in sched]


# ---------------------------------------------------------------- generators
def test_open_schedule_comes_from_the_seed_and_offers_the_same_work():
    spans = (5.0, 30.0, 20.0)
    a = loadgen.open_schedule(7, MIX, spans, 1000)
    b = loadgen.open_schedule(7, MIX, spans, 1000)
    c = loadgen.open_schedule(8, MIX, spans, 1000)
    assert due(a) == due(b) and same_requests(a, b)
    # another seed: other times, another order, other tokens
    assert due(a) != due(c) and not same_requests(a, c)
    assert due(a) == sorted(due(a)) and due(a)[-1] < 55.0
    assert all(512 <= len(s.prompt) <= 3840 and 16 <= s.max_new <= 64 for _, s in a)

    def window(sched):
        return [s for t, s in sched if 5.0 <= t < 35.0]

    # ... of the same work: the count and the lengths of every span
    assert len(window(a)) == len(window(c)) == 150
    assert sorted(len(s.prompt) for s in window(a)) == sorted(len(s.prompt) for s in window(c))
    assert [len(s.prompt) for s in window(a)] != [len(s.prompt) for s in window(c)]
    lens = sorted(len(s.prompt) for s in window(a))
    assert abs(lens[75] - 1536) < 40 and lens[0] == 512 and lens[-1] == 3840


def test_a_mix_with_schedules_plays_them_in_turn():
    spans = (5.0, 30.0, 20.0)
    mix = dict(MIX, schedules=[11, 5, 42])
    a, b, c = (loadgen.open_schedule(s, mix, spans, 1000) for s in (0, 3, 1))
    # runs 0 and 3 play draw 11: the same times and lengths, other tokens
    assert due(a) == due(b) and not same_requests(a, b)
    assert [len(s.prompt) for _, s in a] == [len(s.prompt) for _, s in b]
    assert due(a) != due(c)
    # a listed draw is the draw a free mix makes from that seed
    assert due(c) == due(loadgen.open_schedule(5, MIX, spans, 1000))


def test_a_closed_loop_client_depends_on_mix_seed_index_and_round_alone():
    def req(seed, i, k):
        return loadgen.client_request(seed, i, k, 64, MIX, 1000)

    x, y = req(3, 11, 2), req(3, 11, 2)
    assert np.array_equal(x.prompt, y.prompt) and x.max_new == y.max_new
    assert not np.array_equal(req(3, 11, 2).prompt, req(3, 12, 2).prompt)
    # a round of requests is the same work under every seed, dealt differently
    a = [len(req(3, i, 0).prompt) for i in range(64)]
    b = [len(req(4, i, 0).prompt) for i in range(64)]
    assert sorted(a) == sorted(b) and a != b
    assert sorted(req(3, i, 5).max_new for i in range(64)) == sorted(req(9, i, 1).max_new for i in range(64))


def test_zipf_tokens():
    cdf = loadgen.zipf_cdf(1000, 1.1)
    a = loadgen.token_batch(np.random.default_rng(1), {"law": "zipf"}, cdf, (4, 257), 1000)
    b = loadgen.token_batch(np.random.default_rng(1), {"law": "zipf"}, cdf, (4, 257), 1000)
    assert a.dtype == np.int32 and a.shape == (4, 257) and np.array_equal(a, b)
    assert 0 <= a.min() and a.max() < 1000
    assert (a == 0).mean() > 5 * (a == 50).mean()  # rank 1 far above rank 51


# ---------------------------------------------------------------- percentiles
def test_percentile_by_hand():
    xs = [10.0, 20.0, 30.0, 40.0, 50.0]
    assert stats.percentile(xs, 0) == 10.0 and stats.percentile(xs, 100) == 50.0
    assert stats.percentile(xs, 50) == 30.0
    assert stats.percentile(xs, 90) == pytest.approx(46.0)   # rank 3.6
    assert stats.percentile([], 90) is None
    assert stats.spread([100.0, 101.0, 102.0, 103.0]) == pytest.approx(1.5 / 101.5)


def test_block_marks_end_where_a_burst_ends():
    # a step delivers its tokens in a burst that a poll may cut in two: 32 tokens
    # every 100 ms, of which a poll sees 10 first and the rest one poll later
    marks = stats.BlockMarks(every=64)
    count = 0
    for ms in range(1000):
        if ms % 100 == 50:
            count += 10
        elif ms % 100 == 51:
            count += 22
        marks.see(ms / 1000.0, count)
    # a mark at the first burst's end, then one every two bursts, each at the
    # poll that saw the burst's second half
    assert marks.marks == [(0.051, 32), (0.251, 96), (0.451, 160), (0.651, 224), (0.851, 288)]
    assert stats.block_rates(marks.marks) == pytest.approx([320.0] * 4)
    assert stats.median_rate([]) is None and stats.slowest_block_pct([(0.0, 0)]) is None


def serve_record():
    def q(due, first, finish, n_out, state="finished", submit=None, admitted=None):
        return {"due": due, "submit": due + 0.001 if submit is None else submit,
                "admitted": admitted, "first": first, "finish": finish, "n_out": n_out,
                "max_new": n_out, "state": state, "prompt_len": 10}

    return {
        "t_window0": 100.0, "t_window1": 110.0, "t_proc0": 40.0,
        "requests": [
            q(99.0, 99.5, 101.0, 4),                  # due before the window: no ttft, but
                                                      #   finished in it: tpot 0.5 s
            q(101.0, 101.2, 103.2, 11, admitted=101.05),   # ttft 0.2, tpot 0.2
            q(102.0, 102.4, 104.4, 21, admitted=102.10),   # ttft 0.4, tpot 0.1
            q(103.0, None, 103.5, 0, state="failed"),      # failed: misses both
            q(109.0, 109.1, 112.0, 30),               # ttft 0.1; finished after: no tpot
        ],
        "snapshots": {0: {"t": 100.0, "generated": 50, "counters": {"engine_steps_total": 10, "decode_tokens_total": 50}},
                      1: {"t": 110.0, "generated": 250, "counters": {"engine_steps_total": 60, "decode_tokens_total": 250}}},
        # a mark every 40 tokens: 20 tokens/s, but for one block in which the host stood still for 2 s
        "marks": [(100.0, 50), (102.0, 90), (104.0, 130), (108.0, 170), (110.0, 210)],
        "spans": [("engine.dispatch", 101.0, 101.002), ("engine.dispatch", 102.0, 102.004),
                  ("engine.dispatch", 99.0, 99.5), ("engine.device_wait", 101.0, 101.010)],
    }


def reader(name):
    return importlib.import_module(f"benchmarks.metrics.{name}").read


def test_serving_metrics_on_hand_made_stamps():
    rec = serve_record()
    assert reader("setup_s")(rec) == 60.0
    # the median block's rate, where the window's total would give (210 - 50) / 10 = 16
    assert reader("gen_tok_s")(rec) == pytest.approx(20.0)
    assert reader("serve_slowest_block_pct")(rec) == pytest.approx(100.0)   # 4 s against 2 s
    assert reader("gen_tok_s")(dict(rec, marks=[])) is None   # an open loop takes no marks
    for q in ("50", "90"):
        assert reader(f"sat_tpot_p{q}_ms")(rec) == reader(f"tpot_p{q}_ms")(rec)
    # ttft over requests due in the window with a first token: 0.2, 0.4, 0.1
    assert reader("ttft_p90_ms")(rec) == pytest.approx(stats.percentile([200, 400, 100], 90))
    # tpot over requests finished in the window: 0.5, 0.2, 0.1
    assert reader("tpot_p90_ms")(rec) == pytest.approx(stats.percentile([500, 200, 100], 90))
    assert reader("tpot_p50_ms")(rec) == pytest.approx(200.0)
    assert reader("rows_per_step")(rec) == pytest.approx(4.0)
    assert reader("queue_wait_p50_ms")(rec) == pytest.approx(75.0)
    assert reader("gen_lag_p99_ms")(rec) == pytest.approx(1.0)
    for form in ("", "sat_"):
        assert reader(form + "dispatch_ms_per_step")(rec) == pytest.approx(3.0)   # the 99.0 span is outside
        assert reader(form + "device_wait_ms_per_step")(rec) == pytest.approx(10.0)
    # an untraced run: nothing to read, and the metric is left out of the line
    for name in ("serve_idle_pct", "sat_idle_pct", "serve_pallas_time_pct", "sat_pallas_time_pct"):
        assert reader(name)(rec) is None


def test_training_metrics_on_hand_made_stamps():
    hf = Catalog().config("qwen3-0.6b")
    # a mark every 10 steps of 250 ms, from the first step's end; in the third
    # block the host stood still for 0.5 s
    rec = {"t_proc0": 0.0, "t_window0": 50.0, "t_window1": 60.5, "steps": 40,
           "marks": [(t, (1 + 10 * i) * 4096) for i, t in enumerate([50.25, 52.75, 55.25, 58.25])],
           "tokens_per_step": 4096, "seq_len": 4096, "hf": hf, "chips": 1,
           "device_kind": "TPU v5 lite", "peak_bytes": 13.5e9, "t_first_done": 30.0}
    # the median block's rate, where the window's total would give 40 * 4096 / 10.5 = 15604
    assert reader("train_tok_s")(rec) == pytest.approx(16384.0)
    assert reader("step_ms")(rec) == pytest.approx(250.0)
    assert reader("train_slowest_block_pct")(rec) == pytest.approx(20.0)   # 3.0 s against 2.5 s
    assert reader("mfu_pct")(rec) == pytest.approx(100 * 16384 * 4.985192448e9 / 197e12)
    assert reader("train_peak_hbm_gb")(rec) == pytest.approx(13.5)
    assert reader("compile_s")(rec) == 30.0
    assert reader("train_idle_pct")(rec) is None and reader("collective_exposed_pct")(rec) is None
    with pytest.raises(KeyError):
        reader("mfu_pct")(dict(rec, device_kind="cpu"))


# ---------------------------------------------------------------- operations
def test_operations_per_token_by_hand():
    cat = Catalog()
    small, big = cat.config("qwen3-0.6b"), cat.config("qwen3-1.7b")
    # 0.6B: layer = 1024*(16+16)*128 + 2048*1024 + 3*1024*3072 = 15,728,640; x28 + 151936*1024
    assert flops.matmul_params(small) == 28 * 15_728_640 + 155_582_464 == 595_984_384
    # attention, forward, per token at 4096: 2 * 2 * 2048 * 2048 per layer
    assert flops.forward_flops_per_token(small, 4096) == 2 * 595_984_384 + 28 * 4 * 2048 * 2048
    assert flops.train_flops_per_token(small, 4096) == pytest.approx(4.985192448e9)
    assert flops.matmul_params(big) == 28 * 50_331_648 + 311_164_928
    assert flops.train_flops_per_token(big, 4096) == pytest.approx(11.731992576e9)
    assert flops.param_count(small) == 596_049_920 and flops.param_count(big) == 1_720_574_976
    assert flops.kv_bytes_per_token(big) == 112 * 1024


# ---------------------------------------------------------------- trace reduction
def test_interval_arithmetic():
    assert xplane.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert xplane.subtract([(0, 10)], [(1, 2), (3, 4), (9, 12)]) == [(0, 1), (2, 3), (4, 9)]
    ev = [("while", 0, 10), ("a", 1, 3), ("b", 3, 4), ("d", 12, 13)]
    assert xplane.self_pieces(ev) == [("while", 0, 1), ("a", 1, 3), ("b", 3, 4),
                                      ("while", 4, 10), ("d", 12, 13)]


def test_short_names():
    full = ('%checkpoint.93 = (bf16[1,16,4096,128]{3,2,1,0:T(8,128)(2,1)}, bf16[1,16,4096,128]'
            '{3,2,1,0:T(8,128)(2,1)S(1)}) custom-call(bf16[1,16,4096,128]{3,2,1,0:T(8,128)(2,1)} '
            '%copy-done.43), custom_call_target="tpu_custom_call", operand_layout_constraints={}')
    assert xplane.short_name(full) == "checkpoint custom-call:tpu_custom_call"
    assert xplane.PALLAS.search(xplane.short_name(full))
    fus = ("%fusion.184.remat2 = bf16[4096,1024]{1,0:T(8,128)(2,1)} fusion(bf16[1]{0} %p), "
           "kind=kOutput, calls=%fused_computation.124.clone")
    assert xplane.short_name(fus) == "fusion fusion:kOutput"
    ag = "%all-gather-start.7 = (bf16[8,128]{1,0}, bf16[32,128]{1,0}) all-gather-start(bf16[8,128]{1,0} %x)"
    assert xplane.short_name(ag) == "all-gather-start all-gather-start"
    assert xplane.COLLECTIVE.search(xplane.short_name(ag))
    reader_of_one = "%fusion.9 = bf16[8]{0} fusion(bf16[8]{0} %all-gather-done.7), kind=kLoop"
    assert not xplane.COLLECTIVE.search(xplane.short_name(reader_of_one))
    gte = "%get-tuple-element.5 = bf16[8]{0} get-tuple-element((bf16[8]{0}) %custom-call.3), index=0"
    assert not xplane.PALLAS.search(xplane.short_name(gte))
    assert xplane.short_name("plain name") == "plain name"


def test_reduce_on_a_hand_made_trace():
    def hlo(name, opcode, extra=""):
        return f"%{name} = bf16[8]{{0}} {opcode}(bf16[8]{{0}} %all-gather-done.1){extra}"

    ops = [(hlo("while.1", "while"), 0.0, 6.0), (hlo("fusion.1", "fusion"), 0.0, 2.0),
           (hlo("all-gather-start.1", "all-gather-start"), 2.0, 2.5),
           (hlo("fusion.2", "fusion"), 2.5, 4.0),
           (hlo("all-gather-done.1", "all-gather-done"), 4.0, 5.0),
           (hlo("flash.7", "custom-call", ', custom_call_target="tpu_custom_call"'), 5.0, 6.0),
           (hlo("fusion.3", "fusion"), 8.0, 9.0)]
    trace = {"devices": {0: {"XLA Ops": ops, "Steps": [("step", 0.0, 10.0)]},
                         1: {"XLA Ops": [(hlo("fusion.1", "fusion"), 0.0, 5.0)]}},
             "host": [("bench.make_batch", 6.1, 7.9), ("bench.traced_window", 0.0, 10.0)]}
    r = xplane.reduce_trace(trace, (0.0, 10.0))
    assert r["window_s"] == 10.0 and r["n_devices"] == 2
    assert r["busy_s_by_device"] == {0: 7.0, 1: 5.0} and r["busy_s"] == 6.0
    assert r["exposed_collective_s"] == pytest.approx(1.5)   # start 0.5 + done 1.0, nothing beside
    assert r["pallas_s"] == pytest.approx(1.0)
    assert dict(r["device_ops"])["fusion fusion"] == pytest.approx(4.5)   # three fusions
    assert "while while" not in dict(r["device_ops"])        # its body leaves it nothing
    gaps = dict(r["idle_gaps"])
    assert gaps["bench.make_batch"] == pytest.approx(2.0) and gaps["unattributed"] == pytest.approx(1.0)


def test_reduce_on_the_recorded_trace():
    path = os.path.join(BENCH, "tests", "data", "tpu_small.xplane.pb")
    if not os.path.exists(path):
        pytest.skip("no recorded trace")
    r = xplane.reduce_trace(xplane.load(path))
    expect = json.load(open(os.path.join(os.path.dirname(path), "expect.json")))
    for key, want in expect.items():
        assert r[key] == pytest.approx(want, rel=1e-6), key
    assert 0 < r["busy_s"] <= r["window_s"]


# ---------------------------------------------------------------- the contract
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def test_benchmark_json_and_its_files():
    b = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    assert set(b) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    e2e = {m["name"] for m in b["end_to_end"]}
    cells = {w["name"] for w in b["workloads"]}
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
        assert callable(reader(m["name"]))
    for m in b["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert m["source"] in ("host_clock", "device_trace") and 0.01 <= m["bound"] <= 0.1
    for m in b["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert m["moves"] in e2e
        moved = next(x for x in b["end_to_end"] if x["name"] == m["moves"])
        assert set(m.get("workloads", cells)) <= set(moved.get("workloads", cells)), m["name"]
    assert sum(w["chips"] == 4 for w in b["workloads"]) <= max(1, len(b["workloads"]) // 4)
    cat = Catalog()
    for w in b["workloads"]:
        assert NAME.match(w["name"]) and len(w["why"]) <= 200 and w["chips"] in (1, 4)
        cell = cat.cell(w["name"])
        assert callable(importlib.import_module(cat.traffic(cell["traffic"])["runner"]).run)
        hf = cat.config(cell["config"])
        assert hf["reduced"] == [] and importlib.import_module(hf["reference"])
    # every metric's reader is in the index: no reader that nothing reports
    listed = {m["name"] for m in b["end_to_end"] + b["per_layer"]}
    assert {fn[:-3] for fn in os.listdir(os.path.join(BENCH, "metrics"))
            if fn.endswith(".py") and fn != "__init__.py"} == listed


def test_the_rehearsal_is_the_real_cell_at_toy_sizes():
    cat, toy = Catalog(), Catalog(rehearse=True)
    assert toy.run_seconds < cat.run_seconds
    for w in cat.index["workloads"]:
        real, small = cat.cell(w["name"]), toy.cell(w["name"])
        # the same cell: name, chips, layout, traffic; only sizes shrink
        assert set(small) == set(real)
        assert all(small[k] == real[k] for k in ("name", "chips", "traffic", "config") + (("layout",) if "layout" in real else ()))
        mix, small_mix = cat.traffic(real["traffic"]), toy.traffic(real["traffic"])
        assert set(small_mix) == set(mix) and small_mix["runner"] == mix["runner"]
        assert small_mix.get("schedules") == mix.get("schedules")
        assert toy.config(real["config"])["hidden_size"] < cat.config(real["config"])["hidden_size"]


def test_a_cell_may_not_pin_a_policy():
    from types import SimpleNamespace

    from benchmarks.harness import serve

    cell = {"serve_args": {"--block-size": 16, "--decode-steps": 8}}
    with pytest.raises(SystemExit, match="decode-steps"):
        serve.build(SimpleNamespace(cell=cell, devices=[None], hf={}, seed=0, trace=False))


def test_the_harness_names_no_cell():
    b = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    names = [w["name"] for w in b["workloads"]] + [c["name"] for c in b["configs"]] \
        + [w["traffic"] for w in b["workloads"]]
    for root in ("harness", "metrics"):
        for fn in os.listdir(os.path.join(BENCH, root)):
            if fn.endswith(".py"):
                text = open(os.path.join(BENCH, root, fn)).read()
                assert not [n for n in names if n in text], fn
    assert not [n for n in names if n in open(os.path.join(BENCH, "run.py")).read()]
