"""Record a small profiler trace on the chip, for the trace reduction's unit
test (``benchmarks/tests/data``). A few calls of a small jitted program over
every device this machine has: matrix multiplications in a scan, an all-reduce
where there is more than one device, and a host annotation between calls.

    python3 -m benchmarks.tools.record_trace --out chiprun_out/trace_small

Writes ``<out>/tpu_small.xplane.pb``, ``describe.txt`` and ``expect.json`` (what
``reduce_trace`` gives on it today; a later change to the reduction that
moves these numbers has to say why)."""

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from benchmarks.harness import xplane

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit("record_trace runs on the chip")
    mesh = Mesh(devices, ("d",))
    n = len(devices)

    def step(x, w):
        def body(x, wi):
            return jnp.tanh(x @ wi), None
        x, _ = jax.lax.scan(body, x, w)
        return x - jnp.mean(x, axis=0, keepdims=True)   # an all-reduce over rows when sharded

    x = jax.device_put(jnp.ones((256 * n, 512), jnp.bfloat16), NamedSharding(mesh, P("d", None)))
    w = jax.device_put(jnp.full((4, 512, 512), 0.01, jnp.bfloat16), NamedSharding(mesh, P()))
    f = jax.jit(step)
    f(x, w).block_until_ready()
    tmp = tempfile.mkdtemp(prefix="bench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with jax.profiler.TraceAnnotation(xplane.WINDOW_EVENT):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.call"):
                x = f(x, w)
            with jax.profiler.TraceAnnotation("bench.pause"):
                x.block_until_ready()
                time.sleep(0.002)
    jax.profiler.stop_trace()
    os.makedirs(args.out, exist_ok=True)
    path = os.path.join(args.out, "tpu_small.xplane.pb")
    shutil.copy(xplane.find_xplane(tmp), path)
    shutil.rmtree(tmp, ignore_errors=True)
    with open(os.path.join(args.out, "describe.txt"), "w") as fh:
        fh.write(xplane.describe(path) + "\n")
    r = xplane.reduce_trace(xplane.load(path))
    keep = {k: r[k] for k in ("window_s", "n_devices", "busy_s", "exposed_collective_s", "pallas_s")}
    with open(os.path.join(args.out, "expect.json"), "w") as fh:
        json.dump(keep, fh, indent=1)
    print(json.dumps(keep), os.path.getsize(path))
    return 0


if __name__ == "__main__":
    sys.exit(main())
