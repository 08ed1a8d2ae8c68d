"""Test models (analogue of reference tests/unit/simple_model.py)."""

import jax
import jax.numpy as jnp
import numpy as np


def make_mlp_params(key, hidden=16, nlayers=2, in_dim=16, out_dim=16, dtype=jnp.float32):
    keys = jax.random.split(key, nlayers + 1)
    params = {}
    dims = [in_dim] + [hidden] * (nlayers - 1) + [out_dim]
    for i in range(nlayers):
        params[f"layer_{i}"] = {
            "w": (jax.random.normal(keys[i], (dims[i], dims[i + 1])) * 0.1).astype(dtype),
            "b": jnp.zeros((dims[i + 1],), dtype),
        }
    return params


def mlp_loss_fn(params, batch):
    """MSE regression loss (analogue of reference SimpleModel + random data)."""
    x, y = batch["x"], batch["y"]
    h = x
    n = len(params)
    for i in range(n):
        layer = params[f"layer_{i}"]
        h = h @ layer["w"] + layer["b"]
        if i < n - 1:
            h = jax.nn.relu(h)
    return jnp.mean(jnp.square(h.astype(jnp.float32) - y.astype(jnp.float32)))


def random_dataset(n=64, in_dim=16, out_dim=16, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, in_dim)).astype(np.float32)
    w_true = rng.normal(size=(in_dim, out_dim)).astype(np.float32) * 0.3
    y = (x @ w_true).astype(np.float32)
    return {"x": x, "y": y}


def batch_of(dataset, start, size):
    return {k: v[start : start + size] for k, v in dataset.items()}


def served_tokens(engine, prompts, n_new):
    """``prompts`` through a ``ServingDriver`` over ``engine``, ``n_new`` tokens
    each: every request's generated tokens. A request's uid is its prompt's
    index, as ``engine.generate()`` numbers its rows, so a sampled stream's
    keys (uid, position) are the same on both."""
    from deepspeed_tpu.serving import SamplingParams, ServingDriver

    with ServingDriver(engine) as driver:
        reqs = [driver.submit(p, params=SamplingParams(max_new_tokens=n_new, ignore_eos=True))
                for p in prompts]
        assert [r.uid for r in reqs] == list(range(len(prompts)))
        assert all(r.wait(300) for r in reqs)
    return [list(r.generated) for r in reqs]
