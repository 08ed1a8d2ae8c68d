"""``dstpu generate`` / ``dstpu serve`` — real HF checkpoints end to end.

The last mile of the serving stack (reference bar: real-model checkpoint
loading in reference inference/engine.py:303 + module_inject/
load_checkpoint.py): config.json + safetensors through the arch importer
(models/hf.py, 25 architectures), tokenizer.json through the local
tokenizers runtime, text out through the v1 bucketed-KV engine or the v2
paged/continuous-batching engine — all offline (no network at load time).

    dstpu generate --model /path/to/hf_dir --prompt "Once upon a time" \\
        --max-new-tokens 64 [--engine v2] [--sample --temperature 0.8] \\
        [--tp 2] [--dtype bfloat16]

``serve`` runs the same v2 engine behind the long-lived serving driver +
HTTP front end (deepspeed_tpu/serving/):

    python -m deepspeed_tpu.inference.cli serve --model /path/to/hf_dir \\
        --port 8000 [--num-blocks 512] [--max-context 4096] [--timeout 120]

    curl -N -X POST http://127.0.0.1:8000/generate \\
        -d '{"prompt": "Once upon a time", "max_new_tokens": 64, "stream": true}'
"""

import argparse
import sys

import numpy as np

from deepspeed_tpu.observability.setup_record import get_setup_record, setup_span


def parse_args(argv=None):
    p = argparse.ArgumentParser(
        prog="dstpu generate",
        description="generate text from a local HF checkpoint dir",
    )
    p.add_argument("--model", required=True, help="HF checkpoint directory")
    p.add_argument("--prompt", action="append", default=None,
                   help="prompt text (repeat for a batch)")
    p.add_argument("--prompt-file", default=None,
                   help="file with one prompt per line")
    p.add_argument("--max-new-tokens", type=int, default=64)
    p.add_argument("--engine", choices=["v1", "v2"], default="v1",
                   help="v1 = bucketed KV generate; v2 = paged continuous batching")
    p.add_argument("--dtype", default="bfloat16")
    p.add_argument("--tp", type=int, default=1, help="tensor-parallel ways")
    p.add_argument("--comm-quant", default="none", choices=("none", "int8"),
                   help="quantize collectives int8-inside-the-wire (v2 "
                   "engine, tp>1): the MODEL_AXIS psum behind the "
                   "attention-output and MLP down projections becomes an "
                   "int8 reduce-scatter + all-gather with fp32 block scales")
    p.add_argument("--comm-overlap", default="none", choices=("none", "tiled"),
                   help="tile-granular compute/collective overlap (v2 "
                   "engine, tp>1): split each TP row wire into independent "
                   "per-tile reduce-scatter + all-gather rings the "
                   "scheduler overlaps with compute")
    p.add_argument("--tp-overlap-tiles", type=int, default=4,
                   help="tiles per wire for --comm-overlap tiled")
    p.add_argument("--sample", action="store_true",
                   help="temperature sampling instead of greedy")
    p.add_argument("--temperature", type=float, default=1.0)
    p.add_argument("--top-k", type=int, default=0)
    p.add_argument("--top-p", type=float, default=0.0)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--no-eos", action="store_true", help="ignore the eos token")
    p.add_argument("--tokens-only", action="store_true",
                   help="print token ids instead of decoded text")
    return p.parse_args(argv)


def _load(args):
    from deepspeed_tpu.models import load_hf_model
    from deepspeed_tpu.tokenizer import load_tokenizer

    cfg, params = load_hf_model(args.model, dtype=args.dtype)
    tok = load_tokenizer(args.model)
    return cfg, params, tok


def generate_main(argv=None) -> int:
    args = parse_args(argv)
    prompts = list(args.prompt or [])
    if args.prompt_file:
        with open(args.prompt_file) as f:
            prompts.extend(line.rstrip("\n") for line in f if line.strip())
    if not prompts:
        print("dstpu generate: pass --prompt and/or --prompt-file", file=sys.stderr)
        return 2

    cfg, params, tok = _load(args)
    eos = None if args.no_eos else tok.eos_token_id
    enc = [tok.encode(p) for p in prompts]

    if args.tp > 1:
        from deepspeed_tpu.parallel.topology import Topology, set_topology

        set_topology(Topology(model=args.tp, data=0))

    if args.engine == "v2":
        from deepspeed_tpu.inference.config import RaggedInferenceEngineConfig
        from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2

        max_len = max(len(e) for e in enc) + args.max_new_tokens
        bs = 128
        blocks_per_seq = (max_len + bs - 1) // bs + 1
        rc = RaggedInferenceEngineConfig.from_dict({
            "dtype": args.dtype, "tp_size": args.tp,
            "comm_quant": getattr(args, "comm_quant", "none"),
        "comm_overlap": getattr(args, "comm_overlap", "none"),
        "tp_overlap_tiles": getattr(args, "tp_overlap_tiles", 4),
            "greedy": not args.sample, "temperature": args.temperature,
            "top_k": args.top_k, "top_p": args.top_p, "seed": args.seed,
            "kv_cache": {
                "block_size": bs,
                "num_blocks": max(64, blocks_per_seq * (len(enc) + 1)),
                "max_blocks_per_seq": blocks_per_seq,
            },
            "state_manager": {
                "max_tracked_sequences": max(64, len(enc)),
                "max_ragged_batch_size": 1024,
                "max_ragged_sequence_count": max(8, len(enc)),
                "max_context": max(1024, max_len),
            },
        })
        eng = InferenceEngineV2(cfg, params, rc)
        outs = eng.generate(enc, max_new_tokens=args.max_new_tokens, eos_token_id=eos)
        gen_ids = [np.asarray(o)[len(e):] for o, e in zip(outs, enc)]
    else:
        if args.top_k or args.top_p:
            import sys

            print(
                "warning: --top-k/--top-p are ignored by --engine v1 "
                "(its sampler is temperature-only); use --engine v2 for "
                "filtered sampling",
                file=sys.stderr,
            )
        from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
        from deepspeed_tpu.inference.engine import InferenceEngine

        max_len = max(len(e) for e in enc) + args.max_new_tokens
        ic = DeepSpeedInferenceConfig.from_dict({
            "dtype": args.dtype, "max_tokens": max(4096, max_len),
            "tensor_parallel": args.tp,
            "greedy": not args.sample, "temperature": args.temperature,
            "decode_steps": min(16, args.max_new_tokens),
        })
        eng = InferenceEngine(cfg, ic, params)
        gen_ids = []
        for e in enc:  # v1 batches need equal lengths; serve one at a time
            out = eng.generate(
                e[None], max_new_tokens=args.max_new_tokens,
                greedy=not args.sample, temperature=args.temperature,
                eos_token_id=eos, seed=args.seed,
            )
            gen_ids.append(np.asarray(out)[0, len(e):])

    for prompt, ids in zip(prompts, gen_ids):
        if eos is not None and eos in ids:
            ids = ids[: list(ids).index(eos)]
        if args.tokens_only:
            print(" ".join(str(int(i)) for i in ids))
        else:
            print(tok.decode(ids))
    return 0


def serve_parse_args(argv=None):
    p = _serve_parser(
        prog="dstpu serve",
        description="serve a local HF checkpoint dir over HTTP "
        "(continuous batching, streaming)",
    )
    p.add_argument("--control-port", type=int, default=None, metavar="PORT",
                   help="expose the multi-host control plane on this port "
                   "(0 = ephemeral): remote decode replicas join with "
                   "`dstpu serve-agent --join HOST:PORT`. Needs the "
                   "multi-engine router (--num-decode-replicas > 1 or "
                   "--num-prefill-workers >= 1); cross-process KV "
                   "handoffs additionally need --kv-transport remote")
    p.add_argument("--control-host", default="0.0.0.0",
                   help="interface the control plane binds (agents on "
                   "other machines must be able to reach it)")
    return p.parse_args(argv)


def _serve_parser(prog, description):
    """The shared serve/serve-agent argument surface: everything an
    engine build needs (model, KV pool, TP, spec decode, ...) plus the
    router-side knobs serve-agent simply ignores."""
    p = argparse.ArgumentParser(prog=prog, description=description)
    p.add_argument("--model", required=True, help="HF checkpoint directory")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=8000, help="0 = ephemeral")
    p.add_argument("--dtype", default="bfloat16")
    p.add_argument("--tp", type=int, default=1, help="tensor-parallel ways")
    p.add_argument("--block-size", type=int, default=128)
    p.add_argument("--num-blocks", type=int, default=512, help="KV pool size")
    p.add_argument("--kv-cache-dtype", default="bf16", choices=("bf16", "int8"),
                   help="KV pool payload dtype: int8 quantizes blocks on "
                   "write (per-vector scales, in-kernel dequant) — about "
                   "half the HBM per block, so ~2x blocks per byte budget")
    p.add_argument("--kv-pool-bytes", type=int, default=0,
                   help="size the KV pool from an HBM byte budget instead "
                   "of --num-blocks (the dtype-aware capacity lever: the "
                   "same budget holds ~2x blocks under int8)")
    p.add_argument("--paged-attention-impl", default="auto",
                   choices=("auto", "kernel", "dense"),
                   help="decode attention path: auto = Pallas kernel on "
                   "TPU, dense XLA gather elsewhere")
    p.add_argument("--max-blocks-per-seq", type=int, default=32)
    p.add_argument("--max-context", type=int, default=4096)
    p.add_argument("--max-concurrent", type=int, default=64,
                   help="max tracked sequences (in-engine concurrency)")
    p.add_argument("--max-queue", type=int, default=128,
                   help="admission queue bound (further submits get 503)")
    p.add_argument("--kv-headroom", type=float, default=0.05,
                   help="fraction of KV blocks kept free at admission")
    p.add_argument("--timeout", type=float, default=None,
                   help="default per-request timeout in seconds")
    p.add_argument("--spec-k", type=int, default=0,
                   help="speculative decoding: verify up to this many "
                   "n-gram-drafted tokens per sequence per step (0 = off; "
                   "output stays bit-identical to spec-off)")
    p.add_argument("--spec-ngram", type=int, default=3,
                   help="max n-gram order for the prompt-lookup draft proposer")
    p.add_argument("--comm-quant", default="none", choices=("none", "int8"),
                   help="quantize collectives int8-inside-the-wire (tp>1): "
                   "the TP decode psums run as int8 reduce-scatter + "
                   "all-gather with fp32 block scales; per-wire byte "
                   "counters show up in /metrics")
    p.add_argument("--comm-overlap", default="none", choices=("none", "tiled"),
                   help="tile-granular compute/collective overlap (tp>1): "
                   "split each TP decode wire into independent per-tile "
                   "reduce-scatter + all-gather rings; per-wire tile "
                   "counts show up in /metrics")
    p.add_argument("--tp-overlap-tiles", type=int, default=4,
                   help="tiles per wire for --comm-overlap tiled")
    p.add_argument("--num-prefill-workers", type=int, default=0,
                   help="disaggregated serving: dedicate this many engines "
                   "to chunked prefill; finished prefills hand their KV "
                   "blocks off to a decode replica (0 = colocated)")
    p.add_argument("--num-decode-replicas", type=int, default=1,
                   help="decode replicas behind the router (each owns its "
                   "own KV pool; >1 or --num-prefill-workers >= 1 builds "
                   "the multi-engine Router instead of the single driver)")
    p.add_argument("--placement", default="slo",
                   choices=("slo", "round_robin", "least_loaded"),
                   help="decode-replica placement policy: slo ranks by "
                   "free-block headroom / queue depth / deadline slack")
    p.add_argument("--kv-transport", default="host",
                   choices=("host", "device", "in_process", "remote"),
                   help="KV handoff wire for prefill->decode moves: host "
                   "bounces blocks through portable numpy; device keeps "
                   "exported blocks resident as device arrays and ships "
                   "them in pipelined chunked windows (decode starts "
                   "before the tail lands, no host round-trip); "
                   "in_process is a plain same-process device copy; "
                   "remote stages the host representation at a per-engine "
                   "KVEndpoint and pulls credit-flow-controlled chunk "
                   "windows over a socket (cross-process/host disagg — "
                   "see docs/NETWORKING.md)")
    p.add_argument("--min-decode-replicas", type=int, default=0,
                   help="elastic serving floor: autoscaling never retires "
                   "below this (0 = elastic control plane off)")
    p.add_argument("--max-decode-replicas", type=int, default=0,
                   help="elastic serving ceiling: engines beyond "
                   "--num-decode-replicas spawn as WARM SPARES (step "
                   "programs pre-traced) so scale-up admits requests with "
                   "zero new compilations")
    p.add_argument("--shed-degrade-at", type=float, default=0.5,
                   help="queue occupancy at which non-interactive tiers get "
                   "their max_new_tokens capped")
    p.add_argument("--shed-spec-off-at", type=float, default=0.75,
                   help="queue occupancy at which speculative decoding is "
                   "disabled for non-interactive tiers")
    p.add_argument("--shed-reject-at", type=float, default=0.9,
                   help="queue occupancy at which the lowest QoS tier is "
                   "rejected with 503 + Retry-After")
    p.add_argument("--no-prefix-cache", action="store_true",
                   help="disable automatic prefix caching (on by default "
                   "when serving: repeated prompt prefixes share KV blocks "
                   "and skip their prefill)")
    p.add_argument("--prefix-cache-blocks", type=int, default=0,
                   help="cap on trie-held KV blocks (0 = bounded by pool)")
    p.add_argument("--kv-host-tier-bytes", type=int, default=0,
                   help="host-memory KV tier budget in bytes (0 = off): "
                   "trie-evicted idle blocks spill to a host LRU store and "
                   "re-import through a double-buffered scatter instead of "
                   "re-prefilling; int8 pools pack ~2x the blocks per byte")
    p.add_argument("--kv-host-tier-chunk-blocks", type=int, default=8,
                   help="blocks per double-buffered re-import window")
    p.add_argument("--resilience", action="store_true",
                   help="fault-tolerant serving: step watchdog + replica "
                   "quarantine with probation probes, bit-identical request "
                   "recovery off failed replicas, bounded handoff/pull "
                   "retries (off = legacy fail-fast)")
    p.add_argument("--hung-step-s", type=float, default=5.0,
                   help="watchdog deadline: an engine step older than this "
                   "quarantines its replica and recovers its residents")
    p.add_argument("--max-recoveries", type=int, default=3,
                   help="per-request recovery budget before the stream "
                   "fails instead of ping-ponging across dying replicas")
    p.add_argument("--trace", action="store_true",
                   help="enable end-to-end request tracing: per-request "
                   "span trees + engine-step timeline, served at "
                   "/debug/trace and dumpable with `dstpu trace dump`")
    p.add_argument("--trace-buffer-events", type=int, default=65536,
                   help="total span budget across retained traces and the "
                   "engine timeline ring")
    p.add_argument("--trace-capture", default="all", choices=("all", "slow"),
                   help="retention policy: 'slow' keeps only requests at/"
                   "above the p90 e2e latency plus errors and preemptions")
    p.add_argument("--sample", action="store_true")
    p.add_argument("--temperature", type=float, default=1.0)
    p.add_argument("--top-k", type=int, default=0)
    p.add_argument("--top-p", type=float, default=0.0)
    p.add_argument("--seed", type=int, default=0)
    return p


def serve_agent_parse_args(argv=None):
    p = _serve_parser(
        prog="dstpu serve-agent",
        description="run one decode replica in this process and join a "
        "router's multi-host control plane (dstpu serve must expose one "
        "via Router.serve_control; KV handoffs require "
        "--kv-transport remote on both sides)",
    )
    p.add_argument("--join", required=True, metavar="HOST:PORT",
                   help="the router's control-plane address "
                   "(Router.serve_control)")
    p.add_argument("--name", default=None,
                   help="replica name to register under (default: the "
                   "router assigns the next dN; reusing a name re-joins "
                   "a quarantined replica after a restart)")
    return p.parse_args(argv)


def engine_config_from_args(args, cfg):
    """RaggedInferenceEngineConfig from parsed serve/serve-agent args —
    the one place the CLI surface maps onto engine config, so the router
    process and its remote agents build bit-identical engines from the
    same flags."""
    from deepspeed_tpu.inference.config import RaggedInferenceEngineConfig

    kv_dtype = getattr(args, "kv_cache_dtype", "bf16")
    num_blocks = args.num_blocks
    if int(getattr(args, "kv_pool_bytes", 0) or 0):
        # size the pool from a byte budget: under int8 the same budget
        # holds ~2x blocks (kv_pool.bytes_per_block) — this is where the
        # capacity multiplier reaches admission
        # (a recurrent model's state slots, DeltaNet's or Mamba's, or a mixed stack's window rings,
        # one a tracked sequence and a spare, come out of the same budget first)
        # (a latent model's pool is one plane of one vector a token, kv_layers
        # deep: two planes a layer where a layer holds two attentions; a pool's
        # geometry is its own: the block pool's KV heads and plane widths here,
        # the window pool's inside slot_bytes)
        from deepspeed_tpu.inference.v2.kv_pool import (
            blocks_for_budget, pool_geometry, slot_bytes)

        kv_heads, head_dim, planes = pool_geometry(cfg)
        num_blocks = blocks_for_budget(
            int(args.kv_pool_bytes), args.block_size, kv_heads,
            head_dim, cfg.kv_layers, kv_dtype,
            state_bytes=(args.max_concurrent + 1) * slot_bytes(cfg, args.block_size),
            planes=planes,
        )
    return RaggedInferenceEngineConfig.from_dict({
        "dtype": args.dtype, "tp_size": args.tp,
        "comm_quant": getattr(args, "comm_quant", "none"),
        "comm_overlap": getattr(args, "comm_overlap", "none"),
        "tp_overlap_tiles": getattr(args, "tp_overlap_tiles", 4),
        "greedy": not args.sample, "temperature": args.temperature,
        "top_k": args.top_k, "top_p": args.top_p, "seed": args.seed,
        "spec_k": getattr(args, "spec_k", 0),
        "spec_ngram": getattr(args, "spec_ngram", 3),
        "paged_attention_impl": getattr(args, "paged_attention_impl", "auto"),
        "kv_cache": {
            "block_size": args.block_size,
            "num_blocks": num_blocks,
            "max_blocks_per_seq": args.max_blocks_per_seq,
            "prefix_cache": not getattr(args, "no_prefix_cache", False),
            "prefix_cache_blocks": getattr(args, "prefix_cache_blocks", 0),
            "kv_cache_dtype": kv_dtype,
            "host_tier_bytes": getattr(args, "kv_host_tier_bytes", 0),
            "host_tier_chunk_blocks": getattr(
                args, "kv_host_tier_chunk_blocks", 8
            ),
        },
        "state_manager": {
            "max_tracked_sequences": args.max_concurrent,
            "max_ragged_batch_size": 1024,
            "max_ragged_sequence_count": min(32, args.max_concurrent),
            "max_context": args.max_context,
        },
    })


def _serving_engine(cfg, params, rc):
    """An engine as a serving entry point admits requests into it: every
    shape of the split step is built first (``warm_split_shapes``), so no
    request, whatever its length, compiles one in the middle of its TTFT.
    The programs are traced as a served step traces them, from a thread of
    their own: tracing costs more the deeper the stack that calls it (the
    three shapes of the OLMoE cell: 8.9 s from the benchmark's main thread,
    6.0 s from a fresh one, on one machine in one call: PERF.md, PR 28)."""
    from concurrent.futures import ThreadPoolExecutor

    from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2

    engine = InferenceEngineV2(cfg, params, rc)
    with ThreadPoolExecutor(max_workers=1, thread_name_prefix="serving-warm") as pool:
        pool.submit(engine.warm_split_shapes).result()
    return engine


@setup_span("setup.build_stack")
def build_serving_stack(args, cfg=None, params=None, tok=None):
    """Engine(s) + driver from parsed serve args (split out so tests can
    build the stack without a socket). Pass cfg/params/tok to skip
    checkpoint loading. One engine serves behind ``ServingDriver``; with
    ``--num-decode-replicas`` > 1 or ``--num-prefill-workers`` >= 1 the
    engines (sharing the read-only params, each with its own KV pool) go
    behind the multi-engine ``Router``."""
    from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.serving.cluster import Router
    from deepspeed_tpu.serving.driver import ServingDriver

    if getattr(args, "trace", False):
        from deepspeed_tpu.observability import configure_tracing

        configure_tracing(
            enabled=True,
            max_events=int(getattr(args, "trace_buffer_events", 65536)),
            capture=getattr(args, "trace_capture", "all"),
        )
    if cfg is None or params is None:
        from deepspeed_tpu.models import load_hf_model

        with get_setup_record().span("setup.load_weights"):
            cfg, params = load_hf_model(args.model, dtype=args.dtype)
    if tok is None and args.model:
        from deepspeed_tpu.tokenizer import load_tokenizer

        tok = load_tokenizer(args.model)
    if args.tp > 1:
        from deepspeed_tpu.parallel.topology import Topology, set_topology

        set_topology(Topology(model=args.tp, data=0))
    rc = engine_config_from_args(args, cfg)
    n_prefill = int(getattr(args, "num_prefill_workers", 0) or 0)
    n_decode = int(getattr(args, "num_decode_replicas", 1) or 1)
    if n_prefill < 0 or n_decode < 1:
        raise ValueError(
            f"need num_prefill_workers >= 0 and num_decode_replicas >= 1 "
            f"(got {n_prefill}/{n_decode})"
        )
    # elastic control plane: --min/--max-decode-replicas bound the
    # autoscaler; engines past --num-decode-replicas spawn as warm spares
    elastic_min = int(getattr(args, "min_decode_replicas", 0) or 0)
    elastic_max = int(getattr(args, "max_decode_replicas", 0) or 0)
    elastic_cfg = None
    if elastic_min or elastic_max:
        from deepspeed_tpu.serving.elastic import ElasticServingConfig

        elastic_cfg = ElasticServingConfig(
            min_decode_replicas=max(1, elastic_min),
            max_decode_replicas=max(1, elastic_min, elastic_max, n_decode),
            shed_degrade_at=getattr(args, "shed_degrade_at", 0.5),
            shed_spec_off_at=getattr(args, "shed_spec_off_at", 0.75),
            shed_reject_at=getattr(args, "shed_reject_at", 0.9),
        )
        n_decode = max(n_decode, elastic_cfg.min_decode_replicas)
    resilience_cfg = None
    if getattr(args, "resilience", False):
        from deepspeed_tpu.serving.resilience import ResilienceConfig

        resilience_cfg = ResilienceConfig(
            hung_step_s=float(getattr(args, "hung_step_s", 5.0)),
            max_recoveries=int(getattr(args, "max_recoveries", 3)),
        )
    if (n_prefill == 0 and n_decode == 1 and elastic_cfg is None
            and resilience_cfg is None):
        engine = _serving_engine(cfg, params, rc)
        driver = ServingDriver(
            engine,
            eos_token_id=getattr(tok, "eos_token_id", None),
            max_queue=args.max_queue,
            kv_headroom=args.kv_headroom,
            default_timeout_s=args.timeout,
            spec_ngram=getattr(args, "spec_ngram", 3),
        )
        return driver, tok
    # params are read-only at inference time: every engine shares them,
    # only the per-engine KV pools and scheduler state are separate
    engines = [_serving_engine(cfg, params, rc) for _ in range(n_prefill + n_decode)]
    spare_pool = None
    if elastic_cfg is not None:
        from deepspeed_tpu.serving.elastic import WarmSparePool

        # spares spawn (and pre-trace their step programs) NOW, at build
        # time — scale-up later is pure wiring, zero compiles at admission
        spare_pool = WarmSparePool(
            factory=lambda: InferenceEngineV2(cfg, params, rc),
            count=max(0, elastic_cfg.max_decode_replicas - n_decode),
            warm_kw={"spec_k": int(getattr(args, "spec_k", 0) or 0)},
        )
    router = Router(
        engines=engines,
        num_prefill_workers=n_prefill,
        eos_token_id=getattr(tok, "eos_token_id", None),
        max_queue=args.max_queue,
        kv_headroom=args.kv_headroom,
        default_timeout_s=args.timeout,
        spec_ngram=getattr(args, "spec_ngram", 3),
        placement=getattr(args, "placement", "slo"),
        kv_transport=getattr(args, "kv_transport", "host"),
        elastic=elastic_cfg,
        spare_pool=spare_pool,
        resilience=resilience_cfg,
    )
    return router, tok


def serve_main(argv=None) -> int:
    from deepspeed_tpu.serving.server import start_server

    args = serve_parse_args(argv)
    driver, tok = build_serving_stack(args)
    driver.start()
    if args.control_port is not None:
        if not hasattr(driver, "serve_control"):
            print("dstpu serve: --control-port needs the multi-engine "
                  "router (--num-decode-replicas > 1 or "
                  "--num-prefill-workers >= 1)", file=sys.stderr)
            driver.shutdown()
            return 2
        chost, cport = driver.serve_control(args.control_host,
                                            args.control_port)
        print(f"dstpu serve: control plane on {chost}:{cport} "
              f"(join with `dstpu serve-agent --join HOST:{cport}`)",
              file=sys.stderr)
    server = start_server(driver, host=args.host, port=args.port, tokenizer=tok)
    host, port = server.server_address[:2]
    endpoints = "/generate, /health, /metrics"
    if getattr(args, "trace", False):
        endpoints += ", /debug/trace, /debug/events"
    print(f"dstpu serve: listening on http://{host}:{port} "
          f"({endpoints})", file=sys.stderr)
    try:
        while True:
            import time

            time.sleep(3600)
    except KeyboardInterrupt:
        print("dstpu serve: draining...", file=sys.stderr)
    finally:
        server.shutdown()
        driver.shutdown(drain=True, timeout=60)
    return 0


@setup_span("setup.build_stack")
def build_agent_core(args, cfg=None, params=None, tok=None):
    """One decode ``EngineCore`` for ``dstpu serve-agent`` (split out so
    tests can build an agent without a checkpoint). The engine comes from
    the SAME flag->config mapping as the router's replicas — same seed,
    same sampling keys, so the streams it decodes are bit-identical to a
    local replica's."""
    from deepspeed_tpu.serving.cluster.core import EngineCore
    from deepspeed_tpu.serving.metrics import ServingMetrics

    if cfg is None or params is None:
        from deepspeed_tpu.models import load_hf_model

        with get_setup_record().span("setup.load_weights"):
            cfg, params = load_hf_model(args.model, dtype=args.dtype)
    if tok is None and args.model:
        from deepspeed_tpu.tokenizer import load_tokenizer

        tok = load_tokenizer(args.model)
    if args.tp > 1:
        from deepspeed_tpu.parallel.topology import Topology, set_topology

        set_topology(Topology(model=args.tp, data=0))
    rc = engine_config_from_args(args, cfg)
    engine = _serving_engine(cfg, params, rc)
    core = EngineCore(
        engine, name=args.name or "agent", role="decode",
        kv_headroom=args.kv_headroom,
        spec_k=int(getattr(args, "spec_k", 0) or 0),
        spec_ngram=getattr(args, "spec_ngram", 3),
        metrics=ServingMetrics(),
    )
    return core, tok


def serve_agent_main(argv=None) -> int:
    args = serve_agent_parse_args(argv)
    host, _, port = str(args.join).rpartition(":")
    if not port.isdigit():
        print(f"dstpu serve-agent: --join must be HOST:PORT "
              f"(got {args.join!r})", file=sys.stderr)
        return 2
    join = (host or "127.0.0.1", int(port))
    from deepspeed_tpu.serving.cluster.agent import ReplicaAgent

    core, _tok = build_agent_core(args)
    agent = ReplicaAgent(core, join, name=args.name or None,
                         metrics=core.metrics)
    print(f"dstpu serve-agent: decode replica joining control plane at "
          f"{join[0]}:{join[1]}", file=sys.stderr)
    try:
        return agent.run()
    except KeyboardInterrupt:
        print("dstpu serve-agent: shutting down...", file=sys.stderr)
        agent.close()
        return 0


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else list(argv)
    if argv and argv[0] == "serve":
        return serve_main(argv[1:])
    if argv and argv[0] == "serve-agent":
        return serve_agent_main(argv[1:])
    if argv and argv[0] == "generate":
        argv = argv[1:]
    return generate_main(argv)


if __name__ == "__main__":
    sys.exit(main())
