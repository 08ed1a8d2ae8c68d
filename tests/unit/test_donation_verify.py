"""Tier-B donation regressions: every compiled serving program must alias
EVERY leaf of its pools argument (the class this suite exists to catch: a
donation that names the wrong argument, or misses the int8 scale planes,
copies a whole pool every step), the streamed-adam leaf must alias all four
donated state buffers (including the bf16 param mirror), and fixed-shape
entry points must not retrace across same-shape calls. Aliasing is not
enough: the compiled serving programs must also hold no copy the size of a
KV pool (a layer loop that reads the step-start pool and scatters into the
carried one aliases both pools and still copies each twice a step)."""

import functools

import pytest

import jax.numpy as jnp

from deepspeed_tpu.analysis import verify as dv

PROGRAMS = ["split_step", "decode_only_step", "one_row_step", "verify_step"]


@functools.lru_cache(maxsize=None)
def _programs(kind):
    """(engine, {name: (jitted, args)}) after two same-shape generate()
    passes: pass 1 traces, pass 2 must hit the caches. ``split_step`` holds
    the passes' two prompts as two chunk rows and ``decode_only_step`` is the
    split step's shape for a batch with no chunk row, both as ``generate()``
    ran them; ``one_row_step`` is staged for a batch with one chunk row."""
    if kind in ("gdn", "window"):
        return dv._engine_v2_programs("bf16", model=kind)
    return dv._engine_v2_programs(kind)


# "gdn": a model with Gated DeltaNet layers over a bf16 pool; its pools
# argument ends with the recurrent-state and conv pools, and it has no verify
# step (refused at build). "window": window and global layers in one stack;
# its pools argument ends with the window pools (k, v), likewise no verify step
CASES = [(p, d) for d in ("bf16", "int8", "gdn", "window") for p in PROGRAMS
         if not (d in ("gdn", "window") and p == "verify_step")]


@pytest.mark.parametrize("program,kv_dtype", CASES)
def test_step_programs_alias_every_pool_leaf(program, kv_dtype):
    eng, programs = _programs(kv_dtype)
    assert program in programs, f"harness never hit the {program} path"
    fn, args = programs[program]
    res = dv.check_donation(program, fn, args)
    assert res.ok, res.detail
    assert all(b.aliased for b in res.buffers)
    # the donated buffers ARE the pools' leaves (k, v and, for int8, the two
    # scale planes), and nothing else is donated
    pools = eng._pools()
    assert len(pools) == (2 if kv_dtype == "bf16" else 4)  # gdn, window: two more pools
    got = sorted((tuple(b.shape), b.dtype) for b in res.buffers)
    assert got == sorted((tuple(p.shape), str(p.dtype)) for p in pools)


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8", "gdn", "window"])
@pytest.mark.parametrize("program", ["split_step", "decode_only_step"])
def test_the_steps_generate_ran_trace_once(program, kv_dtype):
    """The two shapes ``generate()`` drives, one step in flight: the second
    pass hit the first's programs, whatever ``last_tokens`` was (zeros, then
    the step before's output)."""
    _, programs = _programs(kv_dtype)
    fn = programs[program][0]
    assert fn._cache_size() == 1
    res = dv.check_recompile(program, fn)
    assert res.ok, res.detail


@pytest.mark.parametrize("program,kv_dtype", CASES)
def test_serving_programs_copy_no_pool(program, kv_dtype):
    eng, programs = _programs(kv_dtype)
    fn, args = programs[program]
    res = dv.check_pool_copies(program, fn, args, eng._pools())
    assert res.ok, res.detail


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8", "gdn", "window"])
@pytest.mark.parametrize("program", ["split_step", "decode_only_step", "one_row_step"])
def test_split_step_takes_the_step_before_its_tokens_and_copies_no_pool(program, kv_dtype):
    """One step in flight: every shape of the split step takes the previous
    one's sampled tokens (``last_tokens``, on the device) and each decode
    slot's source there (``tok_src``), donates the pools alone, and holds no
    pool-sized copy with the two inputs added."""
    eng, programs = _programs(kv_dtype)
    fn, args = programs[program]
    inputs = args[1]
    R = eng.config.state_manager.max_ragged_sequence_count
    assert inputs["last_tokens"].shape == (R + eng.scheduler.max_prompt_chunks,)
    assert inputs["tok_src"].shape == (R,) and inputs["tok_src"].dtype == jnp.int32
    res = dv.check_donation(program, fn, args)
    assert res.ok and len(res.buffers) == len(eng._pools()), res.detail
    res = dv.check_pool_copies(program, fn, args, eng._pools())
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
