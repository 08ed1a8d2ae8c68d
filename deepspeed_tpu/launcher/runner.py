"""``dstpu`` — the launcher CLI (reference ``bin/deepspeed`` →
``launcher/runner.py:436 main``).

Single host:   dstpu train.py --config ds_config.json
Multi host:    dstpu --hostfile hosts.txt train.py ...
Cloud TPU pod: dstpu --tpu my-pod --num_nodes 4 train.py ...

Responsibilities (mirroring the reference):
  * hostfile parsing (``hostname slots=N``, reference runner.py:230-275)
  * ``--include``/``--exclude`` resource filtering (:310)
  * runner selection (pdsh/ssh/gcloud/slurm) + per-host command construction
  * env propagation via ``.dstpu_env`` (the ``.deepspeed_env`` analogue,
    :588) and ``--export`` KEY=VALUE
  * master address/port selection; DSTPU_* bootstrap env that
    ``comm.init_distributed`` consumes

A TPU host runs one process owning all local chips, so "slots" count hosts'
processes (usually 1), not accelerators — accelerator topology comes from
the config's ``mesh`` section.
"""

import argparse
import os
import subprocess
import sys
from pathlib import Path
from typing import Dict

from deepspeed_tpu.launcher.multinode_runner import (
    GcloudRunner,
    PDSHRunner,
    SlurmRunner,
    SSHRunner,
)
from deepspeed_tpu.utils.logging import logger

DSTPU_ENVIRONMENT_NAME = ".dstpu_env"
EXPORT_ENVS = ("PYTHONPATH", "JAX_", "LIBTPU", "TPU_", "XLA_", "DSTPU_")


def parse_args(argv=None):
    p = argparse.ArgumentParser(
        prog="dstpu", description="deepspeed_tpu multi-host launcher",
        formatter_class=argparse.ArgumentDefaultsHelpFormatter,
    )
    p.add_argument("--hostfile", type=str, default="/job/hostfile", help="hostname slots=N lines")
    p.add_argument("--include", type=str, default="", help='e.g. "host1@host2" to select hosts')
    p.add_argument("--exclude", type=str, default="", help='e.g. "host3" to drop hosts')
    p.add_argument("--num_nodes", type=int, default=-1, help="limit to first N hosts (-1 = all)")
    p.add_argument("--master_addr", type=str, default="", help="coordinator address (default: first host)")
    p.add_argument("--master_port", type=int, default=29500)
    p.add_argument("--launcher", type=str, default="", choices=["", "pdsh", "ssh", "gcloud", "slurm"])
    p.add_argument("--tpu", dest="tpu_name", type=str, default="", help="Cloud TPU name (gcloud runner)")
    p.add_argument("--zone", type=str, default="", help="Cloud TPU zone")
    p.add_argument("--remote_python", type=str, default="", help="python interpreter on the workers")
    p.add_argument("--export", action="append", default=[], help="KEY=VALUE to export on every host")
    p.add_argument("--force_multi", action="store_true", help="multi-node path even for one host")
    p.add_argument("--module", action="store_true", help="run user_script with python -m")
    p.add_argument("--no_python", action="store_true", help="exec user_script directly")
    p.add_argument(
        "--autotuning", type=str, default="", choices=["", "tune", "dry"],
        metavar="MODE",
        help="run the autotuner instead of launching: 'tune' (subprocess "
        "experiments over stage/micro/remat-policy/flash-block/shape, "
        "cost-model ordered) or 'dry' (print the ranked candidate space)",
    )
    p.add_argument(
        "--autotuning_preset", type=str, default="bench-767m",
        help="model preset whose shape neighborhood the tuner searches",
    )
    p.add_argument(
        "--autotuning_experiments", type=int, default=12,
        help="experiment budget (each is a fresh subprocess)",
    )
    p.add_argument("user_script", type=str, nargs="?", default="", help="training script (or module with --module)")
    p.add_argument("user_args", nargs=argparse.REMAINDER)
    return p.parse_args(argv)


def run_autotuning(args) -> int:
    """``dstpu --autotuning tune`` (reference ``deepspeed --autotuning`` +
    autotuner.tune()): search the extended space around a preset with real
    subprocess experiments; print the best config as one JSON line."""
    import json

    from deepspeed_tpu.autotuning import (
        Autotuner,
        AutotunerConfig,
        ModelInfo,
        SubprocessRunner,
        estimate_params,
    )
    from deepspeed_tpu.models.transformer import PRESETS

    base = dict(PRESETS[args.autotuning_preset])
    hidden = base.get("hidden_size", 1024)
    heads = base.get("n_heads", 8)
    # shape neighborhood: the preset itself + width/GQA neighbors at a
    # similar parameter budget (the knob family the round-3 MFU wins came
    # from — hand-swept then, searched now)
    head_dim = hidden // heads
    base_kv = base.get("n_kv_heads") or heads
    gqa_ratio = max(1, heads // base_kv)
    shapes = [dict(base)]
    for h_mult, head_mult in ((0.8, 1.0), (1.25, 1.0), (1.0, 0.5)):
        s = dict(base)
        # width neighbors keep the base HEAD DIM and GQA RATIO: the head
        # count snaps to a multiple of the kv count so n_heads % n_kv_heads
        # holds (naive rounding produced only invalid candidates before)
        want = max(1, int(round(heads * h_mult * head_mult)))
        if base_kv == 1:
            # MQA: any head count divides kv=1 — snapping through the ratio
            # would collapse every neighbor back onto the base shape
            new_kv, new_heads = 1, want
        else:
            new_kv = max(1, want // gqa_ratio)
            new_heads = new_kv * gqa_ratio
        s["hidden_size"] = new_heads * head_dim
        s["n_heads"] = new_heads
        if base.get("n_kv_heads"):
            s["n_kv_heads"] = new_kv
        if s["hidden_size"] == hidden and new_heads == heads:
            continue
        shapes.append(s)
    # every experiment is a child that needs the chip, so this parent must
    # never initialise a backend: a child that exits answers instead
    from deepspeed_tpu.accelerator.device import device_peaks, probe_device

    platform, kind = probe_device()
    on_tpu = platform == "tpu"
    # CPU smoke runs are unconstrained
    hbm = device_peaks(kind).hbm_bytes if on_tpu else 64e9
    mi = ModelInfo(
        num_params=estimate_params(base),
        hidden_size=hidden,
        num_layers=base.get("n_layers", 4),
        seq_len=base.get("max_seq_len", 2048),
    )
    cfg = AutotunerConfig(
        enabled=True,
        metric="throughput",
        fast=True,
        max_experiments=args.autotuning_experiments,
        stages=(3,),
        micro_batch_sizes=(2, 4, 6, 8),
        remat_policies=("nothing", "flash", "dots_with_no_batch_dims"),
        flash_blocks=(512, 1024) if on_tpu else (512,),
        # int8 only pays on hardware with a native int8 MXU rate — CPU smoke
        # searches skip it to keep the space small
        matmul_precisions=("default", "int8") if on_tpu else ("default",),
        shapes=tuple(shapes),
    )
    runner = SubprocessRunner(
        metric="mfu_pct" if on_tpu else "tok_s",
        platform=None if on_tpu else "cpu",
        steps=6 if on_tpu else 2,
        warmup=2 if on_tpu else 1,
    )
    tuner = Autotuner(mi, int(hbm), dp_world=1, runner=runner, config=cfg)
    if args.autotuning == "dry":
        for exp in tuner._space()[: args.autotuning_experiments]:
            print(json.dumps(exp))
        return 0
    best, best_val = tuner.tune()
    print(tuner.summary())
    print(json.dumps({"best": best, "metric": best_val}))
    return 0 if best is not None else 1


def parse_hostfile(path: str) -> Dict[str, int]:
    """``hostname slots=N`` per line; '#' comments (reference runner.py:230)."""
    resources: Dict[str, int] = {}
    if not os.path.isfile(path):
        return resources
    for lineno, line in enumerate(Path(path).read_text().splitlines(), 1):
        line = line.split("#")[0].strip()
        if not line:
            continue
        parts = line.split()
        host = parts[0]
        slots = 1
        for tok in parts[1:]:
            if tok.startswith("slots="):
                try:
                    slots = int(tok.split("=", 1)[1])
                except ValueError as e:
                    raise ValueError(f"{path}:{lineno}: bad slots in {line!r}") from e
        if host in resources:
            raise ValueError(f"{path}:{lineno}: duplicate host {host!r}")
        resources[host] = slots
    return resources


def parse_inclusion_exclusion(resources: Dict[str, int], include: str, exclude: str) -> Dict[str, int]:
    """Filter hosts: '@'-separated host names (reference parse_resource_filter
    runner.py:310 — slot-level filtering is meaningless on TPU hosts, where a
    process owns every local chip, so only host granularity is supported)."""
    if include and exclude:
        raise ValueError("--include and --exclude are mutually exclusive")
    if include:
        chosen = {}
        for h in include.split("@"):
            h = h.strip()
            if ":" in h:
                raise ValueError(
                    f"slot-level include {h!r} unsupported on TPU (one process per host)"
                )
            if h not in resources:
                raise ValueError(f"include host {h!r} not in hostfile")
            chosen[h] = resources[h]
        return chosen
    if exclude:
        dropped = {h.strip() for h in exclude.split("@")}
        for h in dropped:
            if h not in resources:
                raise ValueError(f"exclude host {h!r} not in hostfile")
        return {h: s for h, s in resources.items() if h not in dropped}
    return dict(resources)


def collect_env(args) -> Dict[str, str]:
    """Env to propagate: allowlisted prefixes from the current env, the
    ``.dstpu_env`` file (cwd then $HOME, reference .deepspeed_env), and
    explicit --export KEY=VALUE."""
    exports: Dict[str, str] = {}
    for k, v in os.environ.items():
        if any(k.startswith(p) for p in EXPORT_ENVS):
            exports[k] = v
    for base in (Path.cwd(), Path.home()):
        f = base / DSTPU_ENVIRONMENT_NAME
        if f.is_file():
            for line in f.read_text().splitlines():
                line = line.strip()
                if not line or line.startswith("#") or "=" not in line:
                    continue
                k, v = line.split("=", 1)
                exports[k.strip()] = v.strip()
            break
    for kv in args.export:
        if "=" not in kv:
            raise ValueError(f"--export needs KEY=VALUE, got {kv!r}")
        k, v = kv.split("=", 1)
        exports[k] = v
    return exports


def select_runner(args, world_info):
    name = args.launcher
    if not name:
        name = "gcloud" if args.tpu_name else "pdsh"
    cls = {"pdsh": PDSHRunner, "ssh": SSHRunner, "gcloud": GcloudRunner, "slurm": SlurmRunner}[name]
    return cls(args, world_info)


def run_local(args, env: Dict[str, str]) -> int:
    """Single-host path: exec the user script in-place with the env set
    (reference runner.py single-node shortcut)."""
    child_env = {**os.environ, **env}
    child_env.setdefault("DSTPU_NUM_PROCESSES", "1")
    child_env.setdefault("DSTPU_PROCESS_ID", "0")
    if args.no_python:
        cmd = [args.user_script]
    elif args.module:
        cmd = [sys.executable, "-u", "-m", args.user_script]
    else:
        cmd = [sys.executable, "-u", args.user_script]
    cmd += list(args.user_args)
    logger.info(f"dstpu local launch: {' '.join(cmd)}")
    return subprocess.call(cmd, env=child_env)


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if argv and argv[0] == "generate":
        # serve a real checkpoint dir: dstpu generate --model DIR --prompt ...
        from deepspeed_tpu.inference.cli import generate_main

        return generate_main(argv[1:])
    if argv and argv[0] == "serve":
        # long-lived HTTP serving: dstpu serve --model DIR --port 8000
        from deepspeed_tpu.inference.cli import serve_main

        return serve_main(argv[1:])
    if argv and argv[0] == "serve-agent":
        # remote decode replica: dstpu serve-agent --model DIR --join H:P
        from deepspeed_tpu.inference.cli import serve_agent_main

        return serve_agent_main(argv[1:])
    if argv and argv[0] == "lint":
        # static analysis: dstpu lint deepspeed_tpu/ [--verify] [--fail-on error]
        from deepspeed_tpu.analysis.cli import lint_main

        return lint_main(argv[1:])
    if argv and argv[0] == "trace":
        # timeline tooling: dstpu trace dump --url http://HOST:PORT --out X
        from deepspeed_tpu.observability.cli import trace_main

        return trace_main(argv[1:])
    args = parse_args(argv)
    if args.autotuning:
        return run_autotuning(args)
    if not args.user_script:
        print("dstpu: user_script is required (or pass --autotuning tune)", file=sys.stderr)
        return 2
    if args.tpu_name:
        # Cloud TPU: workers are addressed through gcloud + metadata; a
        # hostfile would conflate two addressing schemes, so it is ignored
        if os.path.isfile(args.hostfile):
            logger.warning(f"--tpu given: ignoring hostfile {args.hostfile}")
        n = max(args.num_nodes, 1)
        resources = {f"worker-{i}": 1 for i in range(n)}
        multi = True
    else:
        resources = parse_hostfile(args.hostfile)
        resources = parse_inclusion_exclusion(resources, args.include, args.exclude)
        if args.num_nodes > 0:
            resources = dict(list(resources.items())[: args.num_nodes])
        multi = bool(resources) and (len(resources) > 1 or args.force_multi)

    env = collect_env(args)
    if not multi:
        return run_local(args, env)

    if not args.master_addr:
        args.master_addr = next(iter(resources))
    runner = select_runner(args, resources)
    for k, v in env.items():
        runner.add_export(k, v)
    if not runner.backend_exists():
        raise RuntimeError(f"launcher backend {runner.name!r} not found on PATH")

    if isinstance(runner, SSHRunner):
        procs = []
        for i, host in enumerate(runner.hosts):
            cmd = runner.get_host_cmd(host, i)
            logger.info(f"dstpu ssh[{i}]: {' '.join(cmd)}")
            procs.append(subprocess.Popen(cmd))
        rc = 0
        for p in procs:
            rc = p.wait() or rc  # reap every host; keep the first failure
        return rc

    cmd = runner.get_cmd(dict(os.environ), resources)
    logger.info(f"dstpu {runner.name} launch: {' '.join(cmd[:8])} ...")
    return subprocess.call(cmd)


if __name__ == "__main__":
    sys.exit(main())
