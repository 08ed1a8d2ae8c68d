"""Controls for Kimi Linear through the paged engine (the toy, the oracle and
the helpers of ``tests/unit/test_kimi_linear_serving.py``): faults put into the
served program or its weights that the comparison with the float32 reference
has to catch. Each builds its own engine: a fault changes the traced program."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from tests.unit.test_kimi_linear_serving import (  # noqa: F401 (``model`` is a fixture)
    ATOL, _engine, _reference_logits, _worst, model)


def test_control_one_decay_a_head_fails(model):
    """The decay of a head's FIRST channel given to all its channels (Gated
    DeltaNet's rule in place of KDA's) in the served program."""
    cfg, params = model
    kda = dict(params["layers"]["kda"])
    H, d = cfg.kda_heads, cfg.kda_head_dim
    first = lambda a: jnp.broadcast_to(  # noqa: E731
        a.reshape(a.shape[:-1] + (H, d))[..., :1], a.shape[:-1] + (H, d)).reshape(a.shape)
    kda["kda_dt_bias"], kda["kda_f_b"] = first(kda["kda_dt_bias"]), first(kda["kda_f_b"])
    bad = {**params, "layers": {**params["layers"], "kda": kda}}
    assert _worst(_engine(cfg, bad), params) > 200 * ATOL


def test_control_a_bf16_state_pool_fails(model):
    cfg, params = model
    eng = _engine(cfg, params)
    eng._rec_state = eng._rec_state.astype(jnp.bfloat16)
    assert _worst(eng, params) > 20 * ATOL


def test_control_rotary_on_the_shared_dims_fails(model):
    """``position="rope"``: the shared key dims and the queries' turned, where
    the model (``mla_use_nope``) has no position term."""
    cfg, params = model
    assert _worst(_engine(dataclasses.replace(cfg, position="rope"), params), params) > 200 * ATOL


def test_control_a_latent_plane_at_the_wrong_ordinal_fails(model, monkeypatch):
    """The latent layers reading and writing plane 0 whatever their ordinal."""
    cfg, params = model
    eng = _engine(cfg, params)
    ordinal = eng._ordinal
    monkeypatch.setattr(eng, "_ordinal", lambda li: (
        0 if isinstance(li, int) and cfg.layer_kinds[li] == "full" else ordinal(li)))
    assert _worst(eng, params) > 200 * ATOL


def test_control_a_state_lost_between_two_steps_fails(model):
    """Every slot's state zeroed after the prompt: the next token's logits
    miss the reference; the token before agrees."""
    cfg, params = model
    eng = _engine(cfg, params)
    prompt = np.random.default_rng(4).integers(1, 256, size=70).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        eng.scheduler.submit(0, prompt)
        first = np.asarray(eng.step()[0], np.float32)
        eng.scheduler.feedback(0, int(np.argmax(first)))
        eng._rec_state = jnp.zeros_like(eng._rec_state)
        second = np.asarray(eng.step()[0], np.float32)
        eng.scheduler.finish(0)
        want = _reference_logits(params, prompt, np.stack([first, second]))
    np.testing.assert_allclose(first, want[0], atol=ATOL, rtol=0)
    assert np.abs(second - want[1]).max() > 200 * ATOL
