"""Tier-B donation regressions: the compiled split step must alias BOTH
KV-cache pools (the donate_argnums off-by-one class this suite exists to
catch), the streamed-adam leaf must alias all four donated state buffers
(including the bf16 param mirror), and fixed-shape entry points must not
retrace across same-shape calls. Aliasing is not enough: the compiled
serving programs must also hold no copy the size of a KV pool (a layer loop
that reads the step-start pool and scatters into the carried one aliases
both pools and still copies each twice a step)."""

import functools

import pytest

import jax.numpy as jnp

from deepspeed_tpu.analysis import verify as dv


@functools.lru_cache(maxsize=None)
def _programs(kv_dtype):
    """(engine, {name: (jitted, args)}) after two same-shape generate()
    passes: pass 1 traces, pass 2 must hit the caches."""
    return dv._engine_v2_programs(kv_dtype)


@pytest.fixture(scope="module")
def split_step_capture():
    eng, programs = _programs("bf16")
    assert "split_step" in programs, "harness never hit the split-step path"
    return (eng,) + programs["split_step"]


def test_split_step_aliases_both_kv_pools(split_step_capture):
    eng, fn, args = split_step_capture
    res = dv.check_donation("split_step", fn, args)
    assert res.ok, res.detail
    assert len(res.buffers) == 2, [b.render() for b in res.buffers]
    assert all(b.aliased for b in res.buffers)
    # the two donated buffers ARE the k/v pools, not some other leaves
    got = sorted(tuple(b.shape) for b in res.buffers)
    want = sorted((tuple(eng._k_cache.shape), tuple(eng._v_cache.shape)))
    assert got == want


def test_split_step_traces_once(split_step_capture):
    _, fn, _ = split_step_capture
    res = dv.check_recompile("split_step", fn)
    assert res.ok, res.detail


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
@pytest.mark.parametrize("program", ["split_step", "multistep_decode", "verify_step", "row_step"])
def test_serving_programs_copy_no_pool(program, kv_dtype):
    eng, programs = _programs(kv_dtype)
    fn, args = programs[program]
    pools = (eng._k_cache, eng._v_cache) + eng._scale_args()
    assert len(pools) == (4 if kv_dtype == "int8" else 2)
    res = dv.check_pool_copies(program, fn, args, pools)
    assert res.ok, res.detail


def test_pool_copy_check_flags_a_scatter_inside_the_layer_loop():
    """The protocol this check exists to keep out: the loop reads the
    step-start pool as an invariant and scatters into the carried pool, so
    XLA copies the pool into a second buffer and back, for all the
    aliasing. With the scatter after the loop the same program is clean."""
    import jax

    L, N, D = 3, 64, 8
    pool = jnp.zeros((L, N, D), jnp.float32)
    slot = jnp.arange(4, dtype=jnp.int32)

    def read(pool0, li):  # what attention does: gather rows of layer li
        return pool0.reshape(L * N, D)[li * N + slot + 8].sum(0)

    def inside(pool, x):
        def body(li, st):
            x, carried = st
            x = x + read(pool, li)
            carried = carried.reshape(L * N, D).at[li * N + slot].set(x).reshape(L, N, D)
            return x, carried
        return jax.lax.fori_loop(0, L, body, (x, pool))

    def after(pool, x):
        def body(li, st):
            x, side = st
            x = x + read(pool, li)
            return x, jax.lax.dynamic_update_index_in_dim(side, jnp.broadcast_to(x, (4, D)), li, 0)
        x, side = jax.lax.fori_loop(0, L, body, (x, jnp.zeros((L, 4, D), jnp.float32)))
        at = (jnp.arange(L, dtype=jnp.int32)[:, None] * N + slot[None]).reshape(L * 4)
        return x, pool.reshape(L * N, D).at[at].set(side.reshape(L * 4, D)).reshape(L, N, D)

    args = (pool, jnp.ones((D,), jnp.float32))
    for fn, clean in ((inside, False), (after, True)):
        jitted = jax.jit(fn, donate_argnums=0)
        assert dv.check_donation(fn.__name__, jitted, args).ok  # aliased either way
        res = dv.check_pool_copies(fn.__name__, jitted, args, [pool])
        assert res.ok == clean, res.detail
    assert res.kind == "pool-copy"


def test_streamed_adam_leaf_donates_all_state():
    from deepspeed_tpu.runtime.streamed_adam import StreamedAdamW

    opt = StreamedAdamW(chunk_elems=64, overlap=True)
    fn = opt._leaf_jit(quantized=False)
    args = (
        jnp.zeros((128,), jnp.float32),    # grad (not donated)
        jnp.ones((128,), jnp.float32),     # master
        jnp.zeros((128,), jnp.float32),    # mu
        jnp.zeros((128,), jnp.float32),    # nu
        jnp.ones((128,), jnp.bfloat16),    # param mirror
        jnp.float32(1e-3),
        jnp.int32(1),
    )
    res = dv.check_donation("leaf_step", fn, args)
    assert res.ok, res.detail
    # master, mu, nu AND the param mirror — the param is the one that
    # regresses if the update stops writing through the donated buffer
    assert len(res.buffers) == 4
    assert any(b.dtype == "bfloat16" and b.aliased for b in res.buffers)


def test_alias_positions_parses_sharded_attrs():
    # arg attrs under a mesh embed braces inside mhlo.sharding strings; the
    # parser must not lose the aliasing annotation next to them
    txt = (
        'func.func public @main(%arg0: tensor<8xf32> '
        '{mhlo.sharding = "{devices=[8]<=[8]}", tf.aliasing_output = 0 : i32}, '
        '%arg1: tensor<8xf32> {mhlo.sharding = "{replicated}"}) '
        '-> (tensor<8xf32>) {'
    )
    assert dv._alias_positions(txt) == {0: True, 1: False}


@pytest.mark.slow
def test_run_verify_all_pass():
    results, ok = dv.run_verify(verbose=False)
    assert ok, "; ".join(r.render() for r in results if not r.ok)
