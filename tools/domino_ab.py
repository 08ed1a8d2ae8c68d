"""Domino A/B under real tensor parallelism (tp=2) on the 8-device CPU mesh.

Measures: wall-clock fwd+bwd for a 4-layer TP stack with n_chunks in {1,2,4},
plus HLO schedule evidence — whether the chunked form produces independent
per-chunk all-reduces that a latency-hiding scheduler can interleave.
"""
import os
import re
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.models import get_config, init_params, param_partition_specs
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.parallel.topology import Topology, reset_topology, set_topology
from deepspeed_tpu.runtime.domino.transformer import domino_transformer_layer

reset_topology()
topo = Topology(model=2, data=4)
set_topology(topo)

cfg = get_config(
    "tiny", vocab_size=1024, hidden_size=512, n_layers=4, n_heads=8,
    n_kv_heads=8, max_seq_len=256, dtype="float32", remat=False,
)
params = init_params(cfg, jax.random.key(0))
specs = param_partition_specs(cfg)
params = jax.device_put(
    params, jax.tree.map(lambda s: NamedSharding(topo.mesh, s), specs,
                         is_leaf=lambda x: isinstance(x, P)),
)
B, S = 8, 256
x = jax.device_put(
    jnp.asarray(np.random.default_rng(0).standard_normal((B, S, cfg.hidden_size)), jnp.float32),
    NamedSharding(topo.mesh, P("data", None, None)),
)
positions = jnp.arange(S, dtype=jnp.int32)


def stack_loss(params, x, n_chunks):
    def body(h, i):
        lp = jax.tree.map(lambda l: l[i], params["layers"])
        h, _ = domino_transformer_layer(cfg, lp, h, positions, None, n_chunks=n_chunks)
        return h, None

    # python loop over layers (match domino's peer-program requirement)
    h = x
    for i in range(cfg.n_layers):
        h, _ = body(h, i)
    return jnp.sum(h * h)


results = {}
for n_chunks in (1, 2, 4):
    f = jax.jit(jax.value_and_grad(stack_loss), static_argnums=(2,))
    v, g = f(params, x, n_chunks)
    jax.block_until_ready((v, g))
    t0 = time.perf_counter()
    reps = 20
    for _ in range(reps):
        v, g = f(params, x, n_chunks)
    jax.block_until_ready((v, g))
    dt = (time.perf_counter() - t0) / reps * 1e3
    results[n_chunks] = dt
    print(f"n_chunks={n_chunks}: {dt:.2f} ms/step (fwd+bwd, tp2xdp4, 4 layers)")

# numerics parity
v1, _ = jax.jit(jax.value_and_grad(stack_loss), static_argnums=(2,))(params, x, 1)
v2, _ = jax.jit(jax.value_and_grad(stack_loss), static_argnums=(2,))(params, x, 2)
print(f"exactness: |loss1 - loss2| = {abs(float(v1) - float(v2)):.2e}")

# HLO schedule evidence: count all-reduces and check independence
for n_chunks in (1, 2):
    hlo = (
        jax.jit(jax.value_and_grad(stack_loss), static_argnums=(2,))
        .lower(params, x, n_chunks)
        .compile()
        .as_text()
    )
    ars = re.findall(r"%?(\S*all-reduce\S*)\s*=\s*(\S+)", hlo)
    shapes = [s for _, s in ars]
    print(f"n_chunks={n_chunks}: {len(ars)} all-reduce ops; payload shapes {sorted(set(shapes))[:4]}")
print(f"speedup chunks2 vs 1: {results[1] / results[2]:.3f}x; chunks4 vs 1: {results[1] / results[4]:.3f}x")
