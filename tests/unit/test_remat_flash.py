"""What a remat'd layer keeps of its attention kernel. A policy that keeps the
layer's matrix products (``dots_with_no_batch_dims``, the default, and ``dots``)
keeps the flash kernel's output and log-sum-exp too: the backward of a layer
then runs the backward kernel alone (``dstpu_flash_bwd_fused`` at these sizes,
one call a layer; ``dstpu_flash_bwd_dq`` + ``dstpu_flash_bwd_dkv`` only where a
head's dq accumulator outgrows ``flash_pallas.DQ_RESIDENT_BYTES``) and never
``dstpu_flash_fwd`` a second time. Counted in the traced program at every
``jax.checkpoint`` site that takes ``remat_policy()``, and held to the gradients
of the un-remat'd model with the kernel interpreted.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src.ad_checkpoint import saved_residuals

from deepspeed_tpu.models import get_config, init_params, make_loss_fn
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.ops.attention import flash_pallas, sharded
from deepspeed_tpu.parallel.topology import Topology, reset_topology, set_topology
from deepspeed_tpu.runtime.pipe import make_1f1b_loss_fn, make_pipelined_loss_fn

POLICIES = ("nothing", "flash", "flash_qkv", "dots_with_no_batch_dims", "dots", "everything")
SEQ = 128


@pytest.fixture
def one_device():
    """One device, so that attention calls the kernel itself (no shard_map)."""
    reset_topology()
    topo = Topology(devices=jax.devices()[:1])
    set_topology(topo)
    yield topo
    reset_topology()


@pytest.fixture
def interpreted(monkeypatch, one_device):
    """``attention_impl="flash"`` asks for the compiled kernel; here it runs."""
    def flash(*a, **kw):
        return flash_pallas.flash_attention(*a, **{**kw, "interpret": True})

    monkeypatch.setattr(sharded, "flash_attention", flash)


def _tiny(**kw):
    # GQA group 2, causal, two layers; the flash kernel whatever the platform
    return get_config("tiny", n_kv_heads=2, dtype="float32", attention_impl="flash",
                      max_seq_len=SEQ, **kw)


def _by_kind(**kw):
    # a window layer and a global one whose attention parameters differ in
    # shape: forward() unrolls the stack and checkpoints each kind's layer
    return _tiny(attn_layer_pattern=(1, 0), sliding_window=32, window_kv_heads=1, **kw)


def _batch(cfg, rows=1, segments=False):
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(0, cfg.vocab_size, size=(rows, SEQ + 1)).astype(np.int32)}
    if segments:
        # four packed documents a row, as long as the inputs (the tokens but the last)
        batch["segment_ids"] = np.repeat(np.arange(4, dtype=np.int32), SEQ // 4)[None].repeat(rows, 0)
    return batch


def _kept_by_one_layer(cfg, policy, rows=1):
    """``saved_residuals`` of layer 0 of ``cfg`` under ``jax.checkpoint(policy=policy)``."""
    lp = jax.tree.map(lambda a: a[0], init_params(cfg, jax.random.key(0))["layers"])
    x = jnp.zeros((rows, SEQ, cfg.hidden_size), jnp.float32)
    layer = lambda lp, x: T._layer(cfg, lp, x, jnp.arange(SEQ), None)[0].sum()  # noqa: E731
    return saved_residuals(jax.checkpoint(layer, policy=policy), lp, x)


def kernel_runs(jaxpr, name, times=1):
    """How often the traced program runs the ``pallas_call`` called ``name``: a
    call in the body of a scan counts once a trip."""
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call" and eqn.params["name"] == name:
            n += times
        inner = times * eqn.params["length"] if eqn.primitive.name == "scan" else times
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                sub = getattr(sub, "jaxpr", sub)  # a ClosedJaxpr's
                if hasattr(sub, "eqns"):
                    n += kernel_runs(sub, name, inner)
    return n


def _forward_and_backward_runs(loss, params, batch):
    fwd = kernel_runs(jax.make_jaxpr(loss)(params, batch).jaxpr, flash_pallas.FLASH_FWD)
    both = jax.make_jaxpr(jax.grad(loss))(params, batch).jaxpr
    assert kernel_runs(both, flash_pallas.FLASH_BWD_FUSED) == fwd
    assert kernel_runs(both, flash_pallas.FLASH_BWD_DQ) == kernel_runs(both, flash_pallas.FLASH_BWD_DKV) == 0
    return fwd, kernel_runs(both, flash_pallas.FLASH_FWD) - fwd


@pytest.mark.parametrize("policy", POLICIES)
@pytest.mark.parametrize("site", ["scanned_stack", "layers_by_kind", "pipeline_stage", "pipeline_1f1b"])
def test_backward_runs_the_flash_forward_only_where_nothing_is_kept(
        monkeypatch, one_device, site, policy):
    asked, table = [], T.remat_policy
    monkeypatch.setattr(T, "remat_policy", lambda name: asked.append(name) or table(name))
    cfg = (_by_kind if site == "layers_by_kind" else _tiny)(remat_policy=policy)
    loss, rows = make_loss_fn(cfg), 1
    if site == "pipeline_stage":
        loss = make_pipelined_loss_fn(cfg, micro_batches=1, topo=one_device)
    elif site == "pipeline_1f1b":
        # two stages: the executor's own backward, which is its second site. The
        # kernel does not trace inside the stages' shard_map, so plain attention
        reset_topology()
        topo = Topology(pipe=2, devices=jax.devices()[:2])
        set_topology(topo)
        cfg, rows = dataclasses.replace(cfg, attention_impl="auto"), 2
        both = make_1f1b_loss_fn(cfg, micro_batches=2, topo=topo).custom_value_and_grad
        loss = lambda params, batch: both(params, batch)[0]  # noqa: E731
    params = jax.eval_shape(lambda k: init_params(cfg, k), jax.random.key(0))
    fwd, again = _forward_and_backward_runs(loss, params, _batch(cfg, rows))
    assert asked and set(asked) == {policy}  # the site's policy is the table's
    # the kernel once a layer on the way forward. Attention stacked by kind is
    # plain algebra (no kernel, no tag): those two sites are held to the table alone
    assert fwd == (cfg.n_layers if site in ("scanned_stack", "pipeline_stage") else 0)
    assert again == (fwd if policy == "nothing" else 0)


def test_a_layer_with_no_tagged_kernel_keeps_what_it_kept(one_device):
    # plain XLA attention carries no tag: the default policy's residuals are
    # the products' outputs, as under the products' policy alone
    cfg = dataclasses.replace(_tiny(), attention_impl="reference")

    def kept(policy):
        return sorted((a.shape, str(a.dtype)) for a, _ in _kept_by_one_layer(cfg, policy))

    assert kept(T.remat_policy("dots_with_no_batch_dims")) == kept(
        jax.checkpoint_policies.dots_with_no_batch_dims_saveable)


@pytest.mark.parametrize("model", ["scanned_stack", "layers_by_kind"])
@pytest.mark.parametrize("segments", [False, True], ids=["one_segment", "packed"])
def test_default_policy_has_the_gradients_of_no_remat(interpreted, model, segments):
    cfg = (_by_kind if model == "layers_by_kind" else _tiny)()
    assert cfg.remat and cfg.remat_policy == "dots_with_no_batch_dims"
    params = init_params(cfg, jax.random.key(0))
    batch = _batch(cfg, rows=2, segments=segments)
    grad = lambda c: jax.jit(jax.value_and_grad(make_loss_fn(c)))(params, batch)  # noqa: E731
    loss, grads = grad(cfg)
    loss0, grads0 = grad(dataclasses.replace(cfg, remat=False))
    assert float(loss) == float(loss0) and np.isfinite(float(loss))
    for (path, g), g0 in zip(jax.tree_util.tree_leaves_with_path(grads), jax.tree.leaves(grads0)):
        assert float(jnp.abs(g).max()) > 0, path
        np.testing.assert_array_equal(np.asarray(g), np.asarray(g0), err_msg=str(path))


@pytest.mark.parametrize("policy,impl", [
    ("dots_with_no_batch_dims", "flash"), ("dots", "flash"), ("flash", "flash"),
    ("dots_with_no_batch_dims", "splash")])
def test_a_layer_keeps_the_kernels_output_and_one_float_a_row(interpreted, policy, impl):
    # the memory arithmetic of remat_policy()'s docstring: b·nh·s·d elements of
    # the kernel's output and b·nh·s of its log-sum-exp a layer, not a lane-
    # padded plane of it, whichever kernel carries the tags
    cfg = _tiny(remat_policy=policy)
    if impl == "splash":
        cfg = dataclasses.replace(cfg, attention_impl="splash", sliding_window=64, splash_block=32)
    b, nh, d = 2, cfg.n_heads, cfg.head_dim
    # what the layer keeps of the kernel's making: the two tagged values (the
    # output reads "output of reduce_precision": remat's guard against CSE on a
    # value that is the kernel's primal output and a residual at once)
    kept = {why.split(" from ")[0]: aval
            for aval, why in _kept_by_one_layer(cfg, T.remat_policy(policy), rows=b)
            if f"{impl}_pallas.py" in why}
    assert sorted(a.size for a in kept.values()) == [b * nh * SEQ, b * nh * SEQ * d]
    lse = kept["named 'flash_lse'"]
    assert (lse.size, lse.dtype) == (b * nh * SEQ, jnp.float32)


def _tiny_128(**kw):
    # a head of whole lanes: _attention_block hands the kernel q, k, v where the
    # projections wrote them, [b, s, heads * 128] (PR 59)
    return _tiny(head_dim_override=128, **kw)


@pytest.mark.parametrize("policy", POLICIES)
def test_token_major_operands_keep_their_tags(interpreted, policy):
    # every policy but "nothing" keeps the kernel's output and one float a row
    # of its log-sum-exp, "flash_qkv" q, k and v besides, all token-major: the
    # same arrays at the same sizes as head-major, [b, s, heads * d]
    cfg = _tiny_128(remat_policy=policy)
    b, nh, nkv, d = 2, cfg.n_heads, cfg.kv_heads, cfg.head_dim
    assert d == 128
    kept = [(why.split(" from ")[0], aval)
            for aval, why in _kept_by_one_layer(cfg, T.remat_policy(policy), rows=b)
            if "flash_pallas.py" in why]
    shapes = sorted(a.shape for _, a in kept)
    out, lse, q, kv = (b, SEQ, nh * d), (b, nh, SEQ, 1), (b, SEQ, nh * d), (b, SEQ, nkv * d)
    if policy == "nothing":
        assert shapes == []
    elif policy == "flash_qkv":
        assert shapes == sorted([out, lse, q, kv, kv])
        assert sum(name == "named 'flash_qkv'" for name, _ in kept) == 3
    elif policy == "everything":
        assert out in shapes and lse in shapes
    else:
        assert shapes == sorted([out, lse])
    if policy != "nothing":
        assert dict(kept)["named 'flash_lse'"].dtype == jnp.float32


def test_the_model_hands_the_kernel_token_major_operands(one_device):
    # the traced layer calls the kernels on rank-3 operands at a head of 128
    # and on head-major ones at the tiny preset's head of 32
    def operands(cfg):
        params = jax.eval_shape(lambda k: init_params(cfg, k), jax.random.key(0))
        jaxpr = jax.make_jaxpr(jax.grad(make_loss_fn(cfg)))(params, _batch(cfg))

        def walk(jp):
            for eqn in jp.eqns:
                if eqn.primitive.name == "pallas_call":
                    yield eqn.params["name"], eqn.invars[0].aval.shape
                for v in eqn.params.values():
                    for sub in v if isinstance(v, (tuple, list)) else (v,):
                        sub = getattr(sub, "jaxpr", sub)
                        if hasattr(sub, "eqns"):
                            yield from walk(sub)
        return set(walk(jaxpr.jaxpr))

    cfg = _tiny_128(remat_policy="dots_with_no_batch_dims")
    assert operands(cfg) == {(flash_pallas.FLASH_FWD, (1, SEQ, cfg.n_heads * 128)),
                             (flash_pallas.FLASH_BWD_FUSED, (1, SEQ, cfg.n_heads * 128))}
    cfg = _tiny()
    assert operands(cfg) == {(flash_pallas.FLASH_FWD, (1, cfg.n_heads, SEQ, cfg.head_dim)),
                             (flash_pallas.FLASH_BWD_FUSED, (1, cfg.n_heads, SEQ, cfg.head_dim))}


@pytest.mark.parametrize("segments", [False, True], ids=["one_segment", "packed"])
def test_token_major_gradients_are_those_of_no_remat(interpreted, segments):
    cfg = _tiny_128()
    params = init_params(cfg, jax.random.key(0))
    batch = _batch(cfg, rows=2, segments=segments)
    grad = lambda c: jax.jit(jax.value_and_grad(make_loss_fn(c)))(params, batch)  # noqa: E731
    loss, grads = grad(cfg)
    loss0, grads0 = grad(dataclasses.replace(cfg, remat=False))
    # ... and of the plain reference attention, closely
    loss_ref, grads_ref = grad(dataclasses.replace(cfg, attention_impl="reference"))
    assert float(loss) == float(loss0) and np.isfinite(float(loss))
    assert abs(float(loss) - float(loss_ref)) < 1e-4
    for (path, g), g0, gr in zip(jax.tree_util.tree_leaves_with_path(grads), jax.tree.leaves(grads0),
                                 jax.tree.leaves(grads_ref)):
        assert float(jnp.abs(g).max()) > 0, path
        np.testing.assert_array_equal(np.asarray(g), np.asarray(g0), err_msg=str(path))
        np.testing.assert_allclose(np.asarray(g), np.asarray(gr), rtol=2e-3, atol=2e-4, err_msg=str(path))
