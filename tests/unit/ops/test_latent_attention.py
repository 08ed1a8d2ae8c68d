"""The latent pool's three kernels (ops/attention/latent_pallas.py) against their
dense forms, interpreted on the CPU at a small size, and compiled for a
described v5e at A.X-K1's widths (64 heads against blocks of [576, 128])."""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.attention import latent_pallas as LP

NH, RANK, ROPE, BS, B, P = 4, 16, 8, 16, 5, 23
D = RANK + ROPE
TRASH = P - 1


def _pool(rng):
    return jnp.asarray(rng.normal(size=(P, D, BS)), jnp.float32)


def _tables(rng, tokens, width=B):
    """A table a row over distinct blocks: ``ceil(tokens / BS)`` each."""
    perm, k = rng.permutation(P - 1), 0
    out = np.full((len(tokens), width), TRASH, np.int32)
    for r, n in enumerate(tokens):
        nb = -(-max(int(n), 0) // BS)
        out[r, :nb] = perm[k: k + nb]
        k += nb
    return out


@pytest.mark.parametrize("own", [False, True], ids=["pool_only", "own_vector_beside_the_pool"])
def test_decode_kernel_equals_the_dense_form(own):
    """Rows at a block's first key, inside one, on an edge, across several; a
    padded slot emits 0. With ``own`` the row's new vector rides as a column
    and the pool is read below the row's position."""
    rng = np.random.default_rng(0)
    qpos = np.array([0, 5, 16, 37, -1, 79], np.int32)
    tables = _tables(rng, np.where(qpos >= 0, qpos + 1, 0))
    q = jnp.asarray(rng.normal(size=(len(qpos), NH, D)), jnp.float32)
    extra = limit = None
    if own:
        extra = (jnp.asarray(rng.normal(size=(len(qpos), 1, D)), jnp.float32), jnp.asarray(qpos[:, None]))
        limit = jnp.asarray(np.maximum(qpos, 0))
    kw = dict(rank=RANK, scale=0.3, extra=extra, pool_limit=limit)
    args = (q, _pool(rng), jnp.asarray(tables), jnp.asarray(qpos), TRASH)
    dense = LP.latent_decode(*args, impl="dense", **kw)
    kernel = LP.latent_decode(*args, impl="kernel", **kw)
    np.testing.assert_allclose(np.asarray(kernel), np.asarray(dense), atol=2e-6)
    assert not np.asarray(dense[4]).any() and float(jnp.abs(dense[3]).max()) > 0.1


def test_decode_keeps_what_a_block_holds_outside_the_context_out_of_the_sum():
    """A NaN behind a row's last key (what a freed block may hold) reaches
    neither form's output."""
    rng = np.random.default_rng(1)
    qpos = np.array([20], np.int32)
    tables = _tables(rng, [21])
    pool = np.array(_pool(rng))
    pool[tables[0, 1], :, 6:] = np.nan   # positions 22.. of the row's second block
    q = jnp.asarray(rng.normal(size=(1, NH, D)), jnp.float32)
    for impl in ("dense", "kernel"):
        out = LP.latent_decode(q, jnp.asarray(pool), jnp.asarray(tables), jnp.asarray(qpos), TRASH,
                               rank=RANK, scale=0.3, impl=impl)
        assert np.isfinite(np.asarray(out)).all(), impl


def _chunk_rows(rng, starts, ns, tq, width=B + 3):
    """Chunk rows at ``starts`` with ``ns`` live queries of ``tq``: (q_pos [Rc,
    tq], tables [Rc, width] over distinct blocks)."""
    q_pos = np.full((len(starts), tq), -1, np.int32)
    for r in range(len(starts)):
        q_pos[r, : ns[r]] = starts[r] + np.arange(ns[r])
    return q_pos, _tables(rng, np.asarray(starts) + np.asarray(ns), width=width)


@pytest.mark.parametrize("tile", [8, 16, 32])
def test_chunk_kernel_equals_the_dense_form(tile):
    """The ABSORBED form: a chunk that starts inside a block with a padded
    tail, and a first chunk (nothing below it), at tiles under, at and over the
    block size."""
    rng = np.random.default_rng(2)
    Rc, tq = 2, 64
    starts, ns = np.array([21, 0]), np.array([50, 64])
    q_pos, tables = _chunk_rows(rng, starts, ns, tq)
    q = jnp.asarray(rng.normal(size=(Rc, tq, NH, D)), jnp.float32)
    new = jnp.asarray(rng.normal(size=(Rc, tq, D)), jnp.float32)
    args = (q, _pool(rng), jnp.asarray(tables), jnp.asarray(q_pos), TRASH, new, jnp.asarray(starts))
    dense = LP.latent_chunk_absorbed(*args, rank=RANK, scale=0.3, impl="dense")
    kernel = LP.latent_chunk_absorbed(*args, rank=RANK, scale=0.3, impl="kernel", tile=tile)
    np.testing.assert_allclose(np.asarray(kernel), np.asarray(dense), atol=3e-6)
    assert not np.asarray(dense[0, 50:]).any()   # the padded tail


DN, DV = 8, 8   # a head's key dims without rotary and its value dims, in these tests

# what a row of the EXPANDED kernel may be: (starts, live queries) a row of tq = 64
# over blocks of 16 (a visit is 64 keys)
CHUNK_ROWS = {
    "one_row_on_a_visits_edge": ([64], [64]),
    "two_rows_a_tail_and_a_first_chunk": ([21, 0], [50, 64]),    # the second: no pool below it
    "context_ends_inside_a_visit": ([37], [64]),
    "a_tail_of_one_query": ([70], [1]),
    "a_padded_row_beside_a_live_one": ([16, 0], [64, 0]),
}


def _expanded_inputs(rng, nh, starts, ns, tq=64, dtype=jnp.float32):
    Rc = len(starts)
    q_pos, tables = _chunk_rows(rng, starts, ns, tq)
    rnd = lambda *shape: jnp.asarray(rng.normal(size=shape), dtype)  # noqa: E731
    w = (rnd(RANK, nh * (DN + DV)) * 0.3).astype(dtype)
    # (the query projection as written: a head's [nope | rope] dims on adjacent lanes)
    return (rnd(Rc, tq, nh * (DN + ROPE)), rnd(Rc, tq, nh, ROPE), w, _pool(rng).astype(dtype),
            jnp.asarray(tables), jnp.asarray(q_pos), TRASH, rnd(Rc, tq, D), jnp.asarray(starts))


_WIDE = ("two_rows_a_tail_and_a_first_chunk", "context_ends_inside_a_visit")   # the shapes that differ by row


@pytest.mark.parametrize("rows,nh,heads", (
    [(rows, 4, 2) for rows in CHUNK_ROWS] + [(rows, 8, 8) for rows in CHUNK_ROWS]
    + [(rows, nh, LP.EXPANDED_HEADS) for nh in (64, 32) for rows in _WIDE]))
def test_expanded_chunk_kernel_equals_the_dense_expanded_form(rows, nh, heads):
    """A head's keys and values made of the cached latents inside the kernel
    equal ``kv_b_proj`` of the gathered context in plain jax.numpy, at both
    cells' head counts and at a head-group width under, at and over the one
    the sweep kept."""
    starts, ns = CHUNK_ROWS[rows]
    args = _expanded_inputs(np.random.default_rng(5), nh, starts, ns)
    dense = LP.latent_chunk_expanded(*args, scale=0.3, impl="dense")
    kernel = LP.latent_chunk_expanded(*args, scale=0.3, impl="kernel", heads=heads)
    assert kernel.shape == (len(starts), 64, nh * DV)
    np.testing.assert_allclose(np.asarray(kernel), np.asarray(dense), atol=5e-6)
    for r, n in enumerate(ns):
        assert not np.asarray(dense[r, n:]).any()   # padded queries emit 0
    assert float(jnp.abs(dense).max()) > 0.1


def test_expanded_chunk_stops_at_the_trash_block_and_keeps_nan_out_of_the_sum():
    """A table that names the trash block below the limit it is given (the
    walk's last visit finds it) and a NaN behind the row's context: neither
    form lets either into a sum."""
    rng = np.random.default_rng(6)
    args = list(_expanded_inputs(rng, NH, [60], [64]))
    tables = np.array(args[4])
    tables[0, 4:] = TRASH                       # the row holds 64 tokens
    clean = np.array(args[3])
    clean[TRASH] = np.nan
    args[4] = jnp.asarray(tables)
    for limit in (58, 100):                     # inside the last held block; past what the table holds
        pool = clean.copy()
        pool[tables[0, 3], :, limit - 48:] = np.nan   # positions from the limit on, of the last held block
        args[3], args[8] = jnp.asarray(pool), jnp.asarray([limit])
        dense = LP.latent_chunk_expanded(*args, scale=0.3, impl="dense")
        kernel = LP.latent_chunk_expanded(*args, scale=0.3, impl="kernel", heads=2)
        assert np.isfinite(np.asarray(dense)).all() and np.isfinite(np.asarray(kernel)).all(), limit
        np.testing.assert_allclose(np.asarray(kernel), np.asarray(dense), atol=5e-6)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5), (jnp.bfloat16, 3e-2)], ids=["float32", "bfloat16"])
def test_the_expanded_and_the_absorbed_dense_forms_agree(dtype, tol):
    """The same attention: ``(q_nope W_UK) . c`` is ``q_nope . (W_UK^T c)``.
    In bf16 the two round at different places (the absorbed query against the
    expanded key), which is all that separates them."""
    starts, ns = CHUNK_ROWS["two_rows_a_tail_and_a_first_chunk"]
    q, qr, w, *rest = _expanded_inputs(np.random.default_rng(7), NH, starts, ns, dtype=dtype)
    expanded = LP.latent_chunk_expanded(q, qr, w, *rest, scale=0.3, impl="dense")
    wr = w.reshape(RANK, NH, DN + DV)
    qa = jnp.concatenate([jnp.einsum("rthd,chd->rthc", LP._nope(q, NH, ROPE), wr[..., :DN]), qr], axis=-1)
    absorbed = jnp.einsum("rthc,chd->rthd", LP.latent_chunk_absorbed(
        qa, *rest, rank=RANK, scale=0.3, impl="dense"), wr[..., DN:]).reshape(expanded.shape)
    np.testing.assert_allclose(np.asarray(expanded, np.float32), np.asarray(absorbed, np.float32), atol=tol)


@pytest.mark.parametrize("tq,form", [(128, "absorbed"), (256, "expanded"), (512, "expanded")])
@pytest.mark.parametrize("impl", ["dense", "kernel"])
def test_latent_chunk_picks_the_form_by_the_rows_slots_alone(monkeypatch, impl, tq, form):
    """128 slots a row (one row of at most 128 tokens) absorbed, ``prompt_chunk``
    expanded, on the chip and off it: nothing but ``tq`` is asked."""
    rng = np.random.default_rng(8)
    took = []
    for name in ("absorbed", "expanded"):
        fn = getattr(LP, f"latent_chunk_{name}")
        monkeypatch.setattr(LP, f"latent_chunk_{name}", lambda *a, _f=fn, _n=name, **k: (
            took.append(_n), _f(*a, **{**k, "impl": "dense"}))[1])
    args = _expanded_inputs(rng, NH, [16], [3], tq=tq)
    out = LP.latent_chunk(*args, scale=0.3, impl=impl)
    assert took == [form] and LP.chunk_expands(tq) == (form == "expanded")
    assert out.shape == (1, tq, NH * DV)
    want = LP.latent_chunk_expanded(*args, scale=0.3, impl="dense")
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-5)


def test_the_sweep_tool_counts_what_the_module_header_says():
    """``tools/mla_kernels.py`` prices a score at 2,176 operations absorbed and
    at 640 expanded plus 262,144 a (key, head) shared by a row's queries: 1.9x
    fewer at 512 queries, even at ~171, more at 128."""
    import importlib.util

    here = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
    spec = importlib.util.spec_from_file_location("mla_kernels", os.path.join(here, "tools", "mla_kernels.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    ratio = {}
    for tq in (128, 512):
        absorbed, pool_bytes = tool.needs("absorbed", 1, tq, 64, 8192, products=False)
        expanded, walked = tool.needs("expanded", 1, tq, 64, 8192)
        scores = tq * 64 * (8192 + tq)
        assert absorbed == scores * 2176 and expanded == scores * 640 + 64 * (8192 + tq) * 262144
        ratio[tq] = absorbed / expanded
        # one walk of the context a query tile absorbed, one a HEAD expanded (a group's heads share it)
        assert walked == 64 * (8192 + tq) * 576 * 2 and pool_bytes == walked * (tq // LP.chunk_tile(tq, 64)) // 64
    assert ratio[512] == pytest.approx(2176 / 1152) and ratio[128] < 1 < 2176 / (640 + 262144 / 172)


def test_write_kernel_equals_the_scatter():
    """Decode rows one token a block, two of them in the same block, and a
    chunk that starts inside a block and whose tokens come from two 128-token
    tiles of the step's grid; the padding names the trash block and lands
    nowhere."""
    rng = np.random.default_rng(3)
    L, NBp, n = 3, 9, 150
    pool = jnp.asarray(rng.normal(size=(L, NBp, D, BS)), jnp.float32)
    blk, row = np.full(n, NBp - 1, np.int32), np.zeros(n, np.int32)
    blk[:4], row[:4] = [2, 5, 5, 0], [3, 0, 7, 15]
    pos = 10 + np.arange(40)
    blk[100:140], row[100:140] = np.asarray([1, 3, 4, 6])[pos // BS], pos % BS
    new = jnp.asarray(rng.normal(size=(L, n, D)), jnp.float32)
    visits = LP.write_visits(blk, NBp - 1, 16)
    assert visits[2][:9].tolist() == [3, 3, 3, 3, 3, 1, 3, 3, 0]   # block 4 from two tiles
    dense = LP.latent_write(pool, new, jnp.asarray(blk), jnp.asarray(row), impl="dense")
    kernel = LP.latent_write(pool, new, jnp.asarray(blk), jnp.asarray(row), visits, impl="kernel")
    np.testing.assert_array_equal(np.asarray(kernel[:, :-1]), np.asarray(dense[:, :-1]))
    np.testing.assert_array_equal(np.asarray(kernel[:, 7]), np.asarray(pool[:, 7]))  # untouched
    assert float(jnp.abs(dense - pool)[:, :-1].max()) > 1.0
    # a step that writes nothing is one program: the trash block onto itself
    none = LP.write_visits(np.full(n, NBp - 1), NBp - 1, 4)
    same = LP.latent_write(pool, new, jnp.full(n, NBp - 1), jnp.zeros(n, jnp.int32), none, impl="kernel")
    np.testing.assert_array_equal(np.asarray(same), np.asarray(pool))
    with pytest.raises(RuntimeError, match="sized for 2"):
        LP.write_visits(blk, NBp - 1, 2)


@pytest.mark.parametrize("impl", ["decode", "chunk", "write"])
def test_an_unknown_impl_raises(impl):
    rng = np.random.default_rng(4)
    z = jnp.zeros
    with pytest.raises(ValueError, match="unknown impl"):
        if impl == "decode":
            LP.latent_decode(z((1, NH, D)), _pool(rng), z((1, B), jnp.int32), z(1, jnp.int32), TRASH,
                             rank=RANK, scale=1.0, impl="auto")
        elif impl == "chunk":
            LP.latent_chunk(z((1, BS, NH * (DN + ROPE))), z((1, BS, NH, ROPE)), z((RANK, NH * (DN + DV))), _pool(rng),
                            z((1, B), jnp.int32), z((1, BS), jnp.int32), TRASH, z((1, BS, D)),
                            z(1, jnp.int32), scale=1.0, impl="auto")
        else:
            LP.latent_write(z((1, 2, D, BS)), z((1, 4, D)), z(4, jnp.int32), z(4, jnp.int32), impl="auto")


# -- compiled for the chip, at A.X-K1's widths (no chip: a described v5e) -----------
@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_the_chip(monkeypatch):
    """The kernels ask ``on_tpu()`` whether to interpret; the compile answers."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setattr(LP, "on_tpu", lambda: True)
    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", cached)
    compilation_cache.reset_cache()


# the expanded chunk kernel at the three latent cells' widths: (heads, table
# slots, cache planes, blocks a plane: the cell's pool bytes over 576 x 128 x 2 a block)
_CHUNK_512 = {"chunk_512": (64, 112, 5, 2712), "chunk_512_longcat": (64, 112, 8, 2543),
              "chunk_512_kimi": (32, 272, 3, 7912)}


@pytest.mark.parametrize("what", ["decode", "chunk_128", *_CHUNK_512, "write"])
def test_the_kernels_compile_for_a_v5e_at_the_published_widths(one_chip, on_the_chip, what):
    """Mosaic takes each kernel at 64 heads, blocks of [576, 128] and the
    cell's pool, reads the pool in place (no pool-sized copy in front of the
    call) and, for the write, updates it in place. A chunk row of 128 slots
    compiles the ABSORBED body, one of 512 the EXPANDED one, at A.X-K1's,
    LongCat's and Kimi Linear's heads, table slots and planes: ``wkv_b`` read as
    the checkpoint stores it and the query projection as it was written (no
    copy of either), nothing the size of a row's context times its heads."""
    Dm, bs, rank, R, Rc, dn, dr, dv = 576, 128, 512, 32, 2, 128, 64, 128
    nh, Bt, L, NBp = _CHUNK_512.get(what, _CHUNK_512["chunk_512"])

    def S(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    i32 = jnp.int32
    pool = S((L, NBp, Dm, bs))
    if what == "decode":
        def f(q, pool, tab, pos, ke):
            return LP.latent_decode(q, pool.reshape(L * NBp, Dm, bs), tab, pos, NBp - 1, rank=rank,
                                    scale=0.1, extra=(ke, pos[:, None]), pool_limit=pos, impl="kernel")
        comp = jax.jit(f).lower(S((R, nh, Dm)), pool, S((R, Bt), i32), S((R,), i32),
                                S((R, 1, Dm))).compile()
    elif what.startswith("chunk"):
        tq = int(what.split("_")[1])

        def f(q, qr, w, pool, tab, pos, new, start):
            return LP.latent_chunk(q, qr, w, pool.reshape(L * NBp, Dm, bs), tab, pos, NBp - 1, new,
                                   start, scale=0.1, impl="kernel")
        comp = jax.jit(f).lower(S((Rc, tq, nh * (dn + dr))), S((Rc, tq, nh, dr)), S((rank, nh * (dn + dv))),
                                pool, S((Rc, Bt), i32), S((Rc, tq), i32), S((Rc, tq, Dm)),
                                S((Rc,), i32)).compile()
    else:
        n, G = R + Rc * 512, 64

        def f(pool, new, blk, row, a, b, c):
            return LP.latent_write(pool, new, blk, row, (a, b, c), impl="kernel")
        comp = jax.jit(f, donate_argnums=0).lower(
            pool, S((L, n, Dm)), S((n,), i32), S((n,), i32), S((G,), i32), S((G,), i32),
            S((G,), i32)).compile()
        assert comp.memory_analysis().temp_size_in_bytes < 64 << 20   # no second pool
    text = comp.as_text()
    assert text.count("tpu_custom_call") == 1

    def written(ln):   # elements a copy, transpose or non-bitcast reshape writes
        m = re.match(r"\s*(?:ROOT )?%\S+ = \w+\[([0-9,]+)\]\S* (?:copy|transpose|reshape)\(", ln)
        return int(np.prod([int(x) for x in m[1].split(",")])) if m else 0

    sizes = [written(ln) for ln in text.splitlines()]
    assert max(sizes) < L * NBp * Dm * bs // 2        # nothing the size of the pool
    if what in _CHUNK_512:
        # the weight and the projection go to the kernel as they lie (the rotated
        # rope dims, [.., nh, 64], are the one operand XLA re-lays: half a lane tile
        # a head as the rotary fusion writes them), and the program holds nothing
        # context-sized: a row's keys and values a head would be Bt x 128 x nh x 256
        assert rank * nh * (dn + dv) not in sizes and Rc * 512 * nh * (dn + dr) not in sizes
        assert comp.memory_analysis().temp_size_in_bytes < 24 << 20 < Bt * bs * nh * (dn + dv) * 2


def test_the_flash_backward_compiles_for_a_v5e_at_the_train_cells_shape(one_chip, on_the_chip):
    """The gradient of ``flash_attention`` as both train cells run it in every
    layer (16 query heads over 8 KV heads of 128, a causal 4,096-token
    sequence, bf16, blocks of 1,024): Mosaic takes the one backward kernel with
    a head's 2 MiB dq accumulator resident, inside the scoped-VMEM default, and
    neither streaming kernel is in the program. It stands in this file because
    one process loads the TPU's library: every described compile shares the
    ``topo`` fixture above."""
    from deepspeed_tpu.ops.attention import flash_pallas as FP

    def S(shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        return FP.flash_attention(q, k, v, causal=True).astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        S((1, 16, 4096, 128)), S((1, 8, 4096, 128)), S((1, 8, 4096, 128))).compile().as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert len(calls) == 2     # the forward kernel and ONE backward kernel
    assert sum(FP.FLASH_BWD_FUSED in ln for ln in calls) == 1
    assert FP.FLASH_BWD_DQ not in text and FP.FLASH_BWD_DKV not in text


def test_the_flash_kernels_compile_for_a_v5e_with_every_mask_operand(one_chip, on_the_chip):
    """The same shape with segment ids, ALiBi slopes and positions all present
    (PR 52: each kernel now holds two bodies, the pairs under the diagonal
    without the causal mask and, in the backward, the pairs on it unrolled
    into four strips): forward and fused backward still fit the 16 MiB
    scoped-VMEM default, which the call does not raise."""
    from deepspeed_tpu.ops.attention import flash_pallas as FP

    def S(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    assert FP.causal_pair_classes(4096, 1024, 1024) == (6, 6, 4, 256, 40, 64)

    def grads(q, k, v, seg, slopes, pos, do):
        out, vjp = jax.vjp(lambda q, k, v: FP.flash_attention(
            q, k, v, causal=True, segment_ids=seg, alibi_slopes=slopes, alibi_positions=pos), q, k, v)
        return (out,) + tuple(vjp(do))

    q, kv = S((1, 16, 4096, 128)), S((1, 8, 4096, 128))
    text = jax.jit(grads).lower(q, kv, kv, S((1, 4096), jnp.int32), S((16,), jnp.float32),
                                S((1, 4096), jnp.int32), q).compile().as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert len(calls) == 2
    assert sum(FP.FLASH_FWD in ln for ln in calls) == 1 and sum(FP.FLASH_BWD_FUSED in ln for ln in calls) == 1


def test_an_attention_layer_compiles_for_a_v5e_with_nothing_re_laid_around_the_kernels(
        one_chip, on_the_chip, monkeypatch):
    """``_attention_block`` forward and backward as both train cells run it (16
    query heads over 8 KV heads of 128, per-head RMS norm, rope, 4,096 tokens,
    bf16): the kernels take q, k, v, ``do`` and hand back the output and the
    three gradients as [1, 4096, heads * 128], where the projections write and
    read, and the per-head norm and rope run on the tiles' view of the same
    bytes (``heads_view``). So the compiled layer holds no ``copy``,
    ``transpose`` or ``reshape`` of a [1, 4096, 16 | 8, 128] (or [1, 16 | 8,
    4096, 128]) array: the parent's program had four here, and eleven a layer
    in the whole step (PERF.md, PR 59)."""
    import re

    from deepspeed_tpu.models import transformer as T
    from deepspeed_tpu.ops.attention import core
    from deepspeed_tpu.ops.attention import flash_pallas as FP
    from deepspeed_tpu.parallel.topology import Topology, reset_topology, set_topology

    monkeypatch.setattr(core, "on_tpu", lambda: True)
    c = T.get_config("tiny", hidden_size=1024, n_heads=16, n_kv_heads=8, head_dim_override=128,
                     qk_norm=True, dtype="bfloat16", max_seq_len=4096)
    shapes = jax.eval_shape(lambda k: T.init_params(c, k), jax.random.key(0))["layers"]
    lp = {k: jax.ShapeDtypeStruct(v.shape[1:], jnp.bfloat16, sharding=one_chip)
          for k, v in shapes.items() if k in T.ATTENTION_KEYS}
    x = jax.ShapeDtypeStruct((1, 4096, c.hidden_size), jnp.bfloat16, sharding=one_chip)

    def layer(lp, x, g):
        pos = jnp.arange(4096, dtype=jnp.int32)
        out, vjp = jax.vjp(lambda lp, x: T._attention_block(c, lp, x, pos, None)[0], lp, x)
        return (out,) + vjp(g)

    reset_topology()
    set_topology(Topology(devices=[one_chip._device]))
    try:
        text = jax.jit(layer).lower(lp, x, x).compile().as_text()
    finally:
        reset_topology()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert sum(FP.FLASH_FWD in ln for ln in calls) == 1 and sum(FP.FLASH_BWD_FUSED in ln for ln in calls) == 1
    assert all("bf16[1,4096,2048]" in ln and "[1,16,4096,128]{3,2,1,0:T(8,128)(2,1)" not in ln for ln in calls)
    heads = re.compile(r"\[1,4096,(16|8),128\]|\[1,(16|8),4096,128\]")
    relaid = [ln.split(", metadata")[0] for ln in text.splitlines()
              if re.search(r" (copy|transpose|reshape)\(", ln) and heads.search(ln)]
    assert not relaid, relaid[:3]


def _expanded_chunk_side(text):
    """What the expanded chunk kernel's calls may not have around them in a
    compiled step. Each call's query projection and ``wkv_b`` stack are traced
    back through what moves no byte into another order (bitcasts, tuple
    elements, the compiler's asynchronous slices and prefetches) to the
    operation that wrote them: the projection's own product and the program's
    parameter, never a copy, a transpose or a re-laying reshape (a slice of the
    layer in front of the call, ``latent_up``'s split by heads); and nothing
    re-lays the call's output on its way to the output projection. The rotated
    rope dims alone are re-laid ([.., nh, 64] as the rotary fusion writes them:
    half a lane tile a head)."""
    lines = text.splitlines()
    made = {}
    for ln in lines:
        m = re.match(r"\s*(?:ROOT )?(%[\w.\-]+) = (?:\(.*?\)|\S+) ([\w\-]+)\((.*)", ln)
        if m:   # (the compiler's prefetch of a small stack into fast memory, a slice a layer, joined)
            op = "prefetch" if 'custom_call_target="ConcatBitcast"' in ln else m[2]
            made[m[1]] = (op, re.findall(r"%[\w.\-]+", m[3].split("), ")[0]))
    moves_nothing = {"bitcast", "get-tuple-element", "slice-start", "slice-done", "copy-start", "copy-done",
                     "opt-barrier", "tuple", "prefetch"}

    def origin(name):
        op, operands = made[name]
        return origin(operands[0]) if op in moves_nothing and operands else op

    calls = [n for n, (op, operands) in made.items() if op == "custom-call" and n.startswith("%" + LP.MLA_CHUNK)]
    assert calls
    for call in calls:
        operands = made[call][1]
        # behind the grid's size and the ten scalar operands; q_rope between them
        q, wkv_b = operands[11], operands[13]
        relaid = {"copy", "transpose", "reshape"}
        assert origin(q) not in relaid, (call, "queries", origin(q))
        assert origin(wkv_b) == "parameter", (call, "wkv_b", origin(wkv_b))
        readers = [op for op, operands in made.values() if call in operands]
        assert readers and not relaid & set(readers), (call, readers)


@pytest.mark.parametrize("Rc,tq", [(0, 0), (1, 128), (2, 128), (2, 512)],
                         ids=["decode_only", "one_chunk_row", "two_chunk_rows", "two_expanded_chunk_rows"])
def test_the_cells_split_step_compiles_for_a_v5e_with_no_pool_sized_copy(one_chip, on_the_chip,
                                                                         monkeypatch, Rc, tq):
    """The whole served step of ``a.x-k1.serve-doc-long-closed64`` at its
    sizes: 11.2 GB of weights and the 2 GB pool as arguments, the pool aliased
    to the output, the three latent kernels and the grouped expert matmul in
    it, and no copy the size of the pool (``dstpu lint --verify``'s question,
    asked of the chip's compiler: off the chip the pool is written by XLA's
    scatter, which transposes it). Rows of 128 slots attend absorbed, rows of
    512 EXPANDED (``_expanded_chunk_side``)."""
    import dataclasses
    import json
    import sys

    import deepspeed_tpu.accelerator.device as device
    from deepspeed_tpu.inference.cli import engine_config_from_args, serve_parse_args
    from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.models import init_params
    from deepspeed_tpu.models.hf import config_from_hf

    for name, mod in list(sys.modules.items()):   # every "am I on a TPU?" says yes
        if name.startswith("deepspeed_tpu") and getattr(mod, "on_tpu", None) is not None:
            monkeypatch.setattr(mod, "on_tpu", lambda: True)
    assert device.on_tpu()
    here = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
    hf = json.load(open(os.path.join(here, "benchmarks", "configs", "a.x-k1.json")))
    cell = json.load(open(os.path.join(
        here, "benchmarks", "cells", "a.x-k1.serve-doc-long-closed64.json")))["serve_args"]
    cfg = dataclasses.replace(config_from_hf(hf), dtype="bfloat16")
    argv = ["--model", "", "--port", "0"]
    for flag, value in cell.items():
        argv += [flag, str(value)]
    rc = engine_config_from_args(serve_parse_args(argv), cfg)
    rc.kv_cache = dataclasses.replace(rc.kv_cache, prefix_cache=False)
    shapes = jax.eval_shape(lambda k: init_params(cfg, k), jax.random.key(0))
    eng = InferenceEngineV2(cfg, jax.tree.map(lambda s: jnp.zeros((), s.dtype), shapes), rc)
    assert eng._attn_impl == "kernel"

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    R = rc.state_manager.max_ragged_sequence_count
    _, inputs = eng._stage_split(0, [], [])
    if tq:
        T_, B = R + Rc * tq, rc.kv_cache.max_blocks_per_seq
        grid = {"tokens": T_, "positions": T_, "blk": T_, "row": T_, "chk_tables": (Rc, B),
                "chk_pos": (Rc, tq), "chk_start": Rc, "chk_last": Rc, "chk_uids": Rc}
        inputs = {**inputs, **{k: np.zeros(v, np.int32) for k, v in grid.items()}}
        G = R + Rc * (tq // 128 + tq // LP.WRITE_TILE + 3)
        inputs.update({k: np.zeros(G, np.int32) for k in ("lat_vblk", "lat_vtile", "lat_vflag")})
    pools = tuple(S(p.shape, p.dtype) for p in eng._pools())
    comp = eng._build_split_step((Rc, tq)).lower(
        jax.tree.map(lambda s: S(s.shape, jnp.bfloat16), shapes),
        {k: S(np.shape(v), np.asarray(v).dtype) for k, v in inputs.items()},
        jax.eval_shape(lambda: jax.random.key(0)), S((), jnp.float32), pools).compile()
    ma, text = comp.memory_analysis(), comp.as_text()
    assert ma.alias_size_in_bytes >= 1_999_000_000          # the pool, in place
    assert ma.argument_size_in_bytes < 13_300_000_000 and ma.temp_size_in_bytes < 1_000_000_000
    want = {"dstpu_mla_decode", "dstpu_mla_write", "dstpu_moe_gmm"} | ({"dstpu_mla_chunk"} if tq else set())
    assert want <= set(re.findall(r"dstpu_[a-z_]+", text))
    pool_elems = int(np.prod(pools[0].shape))
    big = [ln for ln in text.splitlines() if " copy(" in ln and any(
        int(np.prod([int(x) for x in dims.split(",")])) >= pool_elems // 2
        for dims in re.findall(r"\[([0-9,]+)\]", ln.split(" copy(")[0])[:1])]
    assert not big, big[:2]
    if LP.chunk_expands(tq):
        _expanded_chunk_side(text)


# -- the paged kernels at MiMo-V2-Flash's geometry, compiled for the same described v5e ---------
# (here and not beside the paged kernels' other tests: one file a topology, one worker a lock)
@pytest.mark.parametrize("nkv,window", [(4, 0), (8, 128)], ids=["full_4_heads", "window_8_heads_sink"])
def test_the_paged_kernels_compile_for_a_v5e_at_a_key_of_192_and_a_value_of_128(
        one_chip, on_the_chip, monkeypatch, nkv, window):
    """``dstpu_paged_decode`` and ``dstpu_paged_chunk`` at 64 heads over 4 and 8
    KV heads, keys of 192 stored a token a row beside values of 128, the sinks
    in a window layer's finish: Mosaic takes both, and the compiler reads both
    pools in place. With the keys a row a HEAD it lays the K pool out
    tokens-minor and copies it whole in front of every call (PR 39)."""
    from deepspeed_tpu.ops.attention import paged_pallas as PP

    monkeypatch.setattr(PP, "on_tpu", lambda: True)
    nh, d, dv, bs, L, NB, R, B, Rc, tq = 64, 192, 128, 128, 2, 400, 32, 184, 2, 512
    sinks = window > 0

    def S(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    i32 = jnp.int32

    def flat(pool):
        return pool.reshape((-1,) + pool.shape[2:])

    def decode(q, kc, vc, tab, pos, ke, ve, sink):
        return PP.paged_attention(
            q, flat(kc), flat(vc), tab, pos, NB, impl="kernel", window=window,
            extra_kv=(ke, ve, pos[:, None]), pool_limit=pos, sinks=sink if sinks else None)

    def chunk(q, kc, vc, tab, pos, ke, ve, start, sink):
        return PP.paged_chunk_attention(
            q, flat(kc), flat(vc), tab, pos, NB, window=window, new_kv=(ke, ve), pool_limit=start,
            impl="kernel", sinks=sink if sinks else None)

    def pools(k_row):
        return S((L, NB + 1, bs) + k_row), S((L, NB + 1, bs, nkv, dv))

    def compiled(k_row):
        return [
            jax.jit(decode).lower(S((R, nh, d)), *pools(k_row), S((R, B), i32), S((R,), i32),
                                  S((R, 1, nkv, d)), S((R, 1, nkv, dv)), S((nh,), jnp.float32)).compile(),
            jax.jit(chunk).lower(S((Rc, tq, nh, d)), *pools(k_row), S((Rc, B), i32), S((Rc, tq), i32),
                                 S((Rc, tq, nkv, d)), S((Rc, tq, nkv, dv)), S((Rc,), i32),
                                 S((nh,), jnp.float32)).compile()]

    pool_bytes = L * (NB + 1) * bs * nkv * d * 2
    assert PP.keys_flat(d) and PP.kernels_take(nkv, d, dv)
    # the decode kernel is given each pool as often as a program reads blocks:
    # four of the full layers' 320 KiB, two of the window layers' 640 KiB
    assert PP.blocks_a_program(bs * nkv * (d + dv) * 2) == {4: 4, 8: 2}[nkv]
    for comp in compiled((nkv * d,)):       # as the engine stores them
        assert comp.as_text().count("tpu_custom_call") == 1
        assert comp.memory_analysis().temp_size_in_bytes < pool_bytes // 8
    # the control: a row a head, and the K pool is copied (and padded to 256 lanes) a call
    assert compiled((nkv, d))[0].memory_analysis().temp_size_in_bytes > pool_bytes


def test_the_paged_decode_kernel_compiles_for_a_v5e_with_the_pool_given_twice(
        one_chip, on_the_chip, monkeypatch):
    """``dstpu_paged_decode`` at Qwen3-1.7B's 16 heads over 8 of 128, the cell's
    32 rows of 32 slots: a program reads TWO blocks of 512 KiB, so the call is
    given the K pool and the V pool twice each, with two index maps over the
    one table. One Mosaic call, and no pool is copied for being given twice."""
    from deepspeed_tpu.ops.attention import paged_pallas as PP

    monkeypatch.setattr(PP, "on_tpu", lambda: True)
    nh, nkv, d, bs, L, NB, R, B = 16, 8, 128, 128, 28, 300, 32, 32
    assert PP.blocks_a_program(bs * nkv * 2 * d * 2) == 2

    def S(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def decode(q, kc, vc, tab, pos, ke, ve):
        flat = lambda pool: pool.reshape((-1,) + pool.shape[2:])
        return PP.paged_attention(q, flat(kc), flat(vc), tab, pos, NB, impl="kernel",
                                  extra_kv=(ke, ve, pos[:, None]), pool_limit=pos)

    pool = S((L, NB + 1, bs, nkv, d))
    comp = jax.jit(decode).lower(
        S((R, nh, d)), pool, pool, S((R, B), jnp.int32), S((R,), jnp.int32),
        S((R, 1, nkv, d)), S((R, 1, nkv, d))).compile()
    assert comp.as_text().count("tpu_custom_call") == 1
    assert comp.memory_analysis().temp_size_in_bytes < L * (NB + 1) * bs * nkv * d * 2 // 8


# (configuration, cell, the kernels its decode-only step calls, the projections
# whose output is split by heads: no copy the size of the smallest may be left)
_BY_KIND = {"dstpu_paged_decode", "dstpu_moe_gmm", "dstpu_stack_matmul"}
MIMO = ("mimo-v2-flash", "mimo-v2-flash.serve-agent-long-closed64", _BY_KIND, lambda layers: ())
K_EXAONE = ("k-exaone-236b-a23b", "k-exaone-236b-a23b.serve-reason-long-closed64", _BY_KIND,
            lambda layers: (layers["wq"], layers["wk"]))
QWEN3 = ("qwen3-1.7b", "qwen3-1.7b.serve-decode-closed64", {"dstpu_paged_decode"},
         lambda layers: (layers["wq"], layers["wk"]))
QWEN3_NEXT = ("qwen3-next-80b-a3b", "qwen3-next-80b-a3b.serve-decode-closed64",
              {"dstpu_paged_decode", "dstpu_moe_gmm", "dstpu_gdn_decode"},
              lambda layers: (layers["gdn"]["gdn_z"], layers["full"]["wk"]))
A_X_K1 = ("a.x-k1", "a.x-k1.serve-doc-long-closed64", {"dstpu_mla_decode", "dstpu_mla_write", "dstpu_moe_gmm"},
          lambda layers: (layers["wq_b"],))
# (a fifth entry: the copies looked for are those of exactly a projection's or
# its stack's size. The chunk step's largest temporaries are ACTIVATIONS laid
# out for the kernels, [64, 512, 1056] the absorbed queries: larger than a
# layer's wq_b. ``wkv_b`` is left out: latent_up splits the WEIGHT by heads, and
# the compiler writes the whole [8, 512, 16384] stack out once a step in front
# of the loop, 134 MB, where A.X-K1's loop writes its five layers' out one by one)
LONGCAT = ("longcat-flash-chat", "longcat-flash-chat.serve-tool-agent-closed64",
           {"dstpu_mla_decode", "dstpu_mla_write", "dstpu_moe_gmm"},
           lambda layers: tuple(w for k, w in layers["sub"].items() if w.ndim == 3 and k != "wkv_b"),
           "exact")


# Kimi Linear: 32 heads, 272 table slots, the latent layers' 3 planes beside the
# KDA layers' state slots, an UNROLLED stack; its chunk rows attend expanded
KIMI = ("kimi-linear-48b-a3b", "kimi-linear-48b-a3b.serve-doc-xlong-closed64",
        {"dstpu_mla_decode", "dstpu_mla_write", "dstpu_moe_gmm", "dstpu_kda_decode"},
        lambda layers: (layers["full"]["wq"],), "exact")


# Jamba2-3B, whole: the block pool at ONE KV head (read as the row it is:
# paged_pallas.one_head; a row a head, the compiler copied both planes, 173 M
# elements each, in front of every call), the state pool [26 x 33, 16, 40, 128]
# float32 and the conv pool beside it, the two state-space kernels
JAMBA = ("jamba2-3b", "jamba2-3b.serve-doc-reason-closed64",
         {"dstpu_paged_decode", "dstpu_mamba_decode"},
         lambda layers: (layers["mamba"]["mamba_in"],))


def _jambas_pools(nb):
    return [(2, nb + 1, 128, 1, 128)] * 2 + [(26 * 33, 16, 40, 128), (26 * 33, 3 * 5120)]


def _mimos_pools(nb):
    return [(2, nb + 1, 128, 4 * 192), (2, nb + 1, 128, 4, 128), (9, 33 * 2, 128, 8 * 192), (9, 33 * 2, 128, 8, 128)]


def _k_exaones_pools(nb):
    return [(2, nb + 1, 128, 8, 128)] * 2 + [(6, 33 * 2, 128, 8, 128)] * 2


# (configuration, cell), chunk rows, their bucket, the pools the engine builds
# (None: not this case's question), the most the program's temporaries may take
@pytest.mark.parametrize("model,Rc,tq,pool_shapes,temp_limit", [
    # (a slice is a temporary: 1.13 / 1.48 GB with them)
    (MIMO, 0, 0, _mimos_pools, 500_000_000),
    (MIMO, 2, 512, _mimos_pools, 500_000_000),
    # K-EXAONE's projections sit in the COMMON stack: 818 / 875 MB at the parent
    # of PR 40 (every layer's wq and wk written out of the stack and re-laid),
    # 6.6 / 273 MB read in place (a chunk step's are its routed rows'
    # activations, [544 x 8, 6144] in float32 the largest; under this suite's
    # XLA_FLAGS: 815 / 856 and 6.6 / 163 MB without them)
    (K_EXAONE, 0, 0, _k_exaones_pools, 100_000_000),
    (K_EXAONE, 1, 512, _k_exaones_pools, 400_000_000),
    # the LOOPED stacks (models.transformer.as_written, PR 42). At its parent a
    # projection split by heads was a product batched over heads, its weight
    # written out of the stack and transposed every layer: two copies a layer
    # (wq and wk; gdn_z and the full layer's wk, and the whole [9, 2048, 4096]
    # gdn_z stack copied in front of the loop; wq_b) and temporaries of 0.54 /
    # 63.7 / 154.3 / 225.8 MB, against 0.48 / 63.7 / 3.1 / 55.5 MB read in place
    # (A.X-K1's are wkv_b's five copies of 16.8 MB: latent_up splits the WEIGHT)
    (QWEN3, 0, 0, None, 1_000_000),
    (QWEN3, 1, 512, None, 70_000_000),
    (QWEN3_NEXT, 0, 0, None, 10_000_000),
    (A_X_K1, 0, 0, None, 80_000_000),
    # LongCat-Flash's sub-block stacks are looped too, indexed at 2 li + i
    (LONGCAT, 0, 0, None, 140_000_000),
    (LONGCAT, 2, 512, None, 1_200_000_000),
    (KIMI, 2, 512, None, 400_000_000),
    # Jamba2-3B: 17.6 / 51.1 MB (a chunk step's are the scan's operands in
    # float32, [1024, 40, 128] each, laid out for the kernel: PR 53)
    (JAMBA, 0, 0, _jambas_pools, 30_000_000),
    (JAMBA, 2, 512, _jambas_pools, 80_000_000),
], ids=["decode_only", "two_chunk_rows", "k_exaone_decode_only", "k_exaone_one_chunk_row",
        "qwen3_decode_only", "qwen3_one_chunk_row", "qwen3_next_decode_only", "a_x_k1_decode_only",
        "longcat_decode_only", "longcat_two_chunk_rows", "kimi_two_chunk_rows", "jamba_decode_only",
        "jamba_two_chunk_rows"])
def test_mimo_v2_flashs_split_step_compiles_for_a_v5e_with_no_pool_sized_copy(
        one_chip, on_the_chip, monkeypatch, model, Rc, tq, pool_shapes, temp_limit):
    """The whole served step of ``mimo-v2-flash.serve-agent-long-closed64`` at
    its sizes: 10.84 GB of weights and both pools as arguments (the block pool
    at 4 KV heads, the window pool at 8, K planes a token a row), the pools
    aliased to the output, both paged kernels and the grouped expert matmul in
    it, the kernels chosen by ``auto``, and no copy the size of a pool. And
    that of ``k-exaone-236b-a23b.serve-reason-long-closed64`` (11.96 GB, two
    pools of one geometry), at the shapes its scheduler cuts most. And those of
    the three cells whose stacks are LOOPED (Qwen3, Qwen3-Next, A.X-K1): a
    projection that is split by heads reads its layer where the stack lies."""
    import dataclasses
    import json
    import re
    import sys

    from deepspeed_tpu.inference.cli import engine_config_from_args, serve_parse_args
    from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.models import init_params
    from deepspeed_tpu.models.hf import config_from_hf

    for name, mod in list(sys.modules.items()):   # every "am I on a TPU?" says yes
        if name.startswith("deepspeed_tpu") and getattr(mod, "on_tpu", None) is not None:
            monkeypatch.setattr(mod, "on_tpu", lambda: True)
    here = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
    config, cell_name, kernels, split_by_heads, *exact = model
    hf = json.load(open(os.path.join(here, "benchmarks", "configs", config + ".json")))
    cell = json.load(open(os.path.join(here, "benchmarks", "cells", cell_name + ".json")))["serve_args"]
    cfg = dataclasses.replace(config_from_hf(hf), dtype="bfloat16")
    argv = ["--model", "", "--port", "0"]
    for flag, value in cell.items():
        argv += [flag, str(value)]
    rc = engine_config_from_args(serve_parse_args(argv), cfg)
    shapes = jax.eval_shape(lambda k: init_params(cfg, k), jax.random.key(0))
    eng = InferenceEngineV2(cfg, jax.tree.map(lambda s: jnp.zeros((), s.dtype), shapes), rc)
    assert eng._attn_impl == "kernel"
    kv = rc.kv_cache
    budget = int(cell["--kv-pool-bytes"])
    held = sum(int(np.prod(p.shape)) * p.dtype.itemsize for p in eng._pools())
    if pool_shapes:
        assert [p.shape for p in eng._pools()] == pool_shapes(kv.num_blocks)
        assert budget - 655_360 < held <= budget      # what kv_pool counts is what the chip holds

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    R = rc.state_manager.max_ragged_sequence_count
    _, inputs = eng._stage_split(0, [], [])
    if tq:
        T_, B = R + Rc * tq, kv.max_blocks_per_seq
        grid = {"tokens": T_, "positions": T_, "blk": T_, "row": T_, "wblk": T_, "chk_tables": (Rc, B),
                "chk_pos": (Rc, tq), "chk_start": Rc, "chk_last": Rc, "chk_uids": Rc, "chk_slots": Rc}
        inputs = {**inputs, **{k: np.zeros(v, np.int32) for k, v in grid.items()}}
        if "lat_vblk" in inputs:  # a latent pool's write visits: a count a step shape
            G = R + Rc * (tq // 128 + tq // LP.WRITE_TILE + 3)
            inputs.update({k: np.zeros(G, np.int32) for k in ("lat_vblk", "lat_vtile", "lat_vflag")})
    pools = tuple(S(p.shape, p.dtype) for p in eng._pools())
    comp = eng._build_split_step((Rc, tq)).lower(
        jax.tree.map(lambda s: S(s.shape, jnp.bfloat16), shapes),
        {k: S(np.shape(v), np.asarray(v).dtype) for k, v in inputs.items()},
        jax.eval_shape(lambda: jax.random.key(0)), S((), jnp.float32), pools).compile()
    ma, text = comp.memory_analysis(), comp.as_text()
    assert ma.alias_size_in_bytes >= held                    # the pools, in place
    assert ma.argument_size_in_bytes + ma.temp_size_in_bytes < 15_000_000_000
    want = kernels | ({"dstpu_mla_chunk" if "lat_vblk" in inputs else "dstpu_paged_chunk"} if tq else set())
    if tq and "dstpu_mamba_decode" in kernels:
        want = want | {"dstpu_mamba_scan"}
    if tq and "dstpu_kda_decode" in kernels:
        want = want | {"dstpu_kda_chunk"}
    assert want <= set(re.findall(r"dstpu_[a-z_]+", text))
    # nor one of a layer's projection: the stacks are read in place
    # (ops/stack_matmul.py; sliced, every wq, wk, wv and wo was written out of
    # its stack a step and the first three transposed again: 1.1 GB of
    # temporaries, 24% of the chip's time; K-EXAONE's smallest, a layer's wk,
    # is 12.6 MB)
    smallest = min([int(np.prod(p.shape)) // 2 for p in pools]
                   + [int(np.prod(w.shape[1:])) for w in split_by_heads(shapes["layers"])])

    def written(ln):   # by a copy, or by a fusion that slices a stack at the layer
        m = re.match(r"\s*(?:ROOT )?%(\S+) = \w+\[([0-9,]+)\]", ln)
        if m and "S(1)}" in ln.split(" copy(")[0]:
            # (into the chip's fast memory: the compiler's own prefetch of a small
            # stack, read once there where a product would read it; Jamba's
            # x_proj stack, 51 MB, PR 53. Nothing is re-laid in HBM)
            return 0
        if m and (" copy(" in ln or "dynamic-slice_fusion" in m[1]):
            return int(np.prod([int(x) for x in m[2].split(",")]))
        return 0

    big = [ln for ln in text.splitlines() if written(ln) >= smallest]
    if exact:
        sizes = {int(np.prod(w.shape[n:])) for w in split_by_heads(shapes["layers"]) for n in (0, 1)}
        sizes |= {int(np.prod(p.shape[n:])) for p in pools for n in (0, 1)}   # nor a pool, nor a plane
        # (from a megabyte on: Kimi's [32, 512, 32] slice of W_UK is a state slot's size)
        big = [ln for ln in text.splitlines() if written(ln) in sizes and written(ln) >= 1 << 20]
    assert not big, big[:2]
    print("temporaries", ma.temp_size_in_bytes)
    assert ma.temp_size_in_bytes < temp_limit
    if "lat_vblk" in inputs and LP.chunk_expands(tq):
        _expanded_chunk_side(text)


# -- ops/stack_matmul.py, compiled for the same described v5e (one file a topology) ---------------
@pytest.mark.parametrize("m", [32, 1056], ids=["decode_rows", "two_chunk_rows"])
def test_the_stack_matmul_kernel_copies_no_layer_where_the_slice_copies_each(one_chip, on_the_chip, m):
    """Three layers' ``wq`` at MiMo-V2-Flash's widths multiplied in turn: read
    in place the program's temporaries are the activations; sliced, XLA writes
    every layer out of the stack first (100 MB each)."""
    from deepspeed_tpu.ops import stack_matmul as SM

    L, k, n = 3, 4096, 12288
    layer = k * n * 2

    def compiled(impl):
        def f(stack, x):
            for i in range(L):
                x = jnp.tanh(SM.stack_matmul(x, stack, i, impl)[:, :k])
            return x
        return jax.jit(f).lower(
            jax.ShapeDtypeStruct((L, k, n), jnp.bfloat16, sharding=one_chip),
            jax.ShapeDtypeStruct((m, k), jnp.bfloat16, sharding=one_chip)).compile()

    in_place = compiled("kernel")
    assert SM.STACK_MATMUL in in_place.as_text()
    assert in_place.memory_analysis().temp_size_in_bytes < layer // 2
    assert compiled("slice").memory_analysis().temp_size_in_bytes >= (L - 1) * layer
