"""``x @ stack[index]`` with the stack read IN PLACE.

A served step of an unrolled stack (layers of two kinds of attention, a dense
lead layer) reaches a layer's weights by a static index into parameters stacked
``[layers, k, n]``. To XLA on the TPU that index is a slice, and a slice is a
copy: it writes every layer's ``[k, n]`` out of the stack at the head of the
program and, where the product behind it wants the other layout, transposes the
copy again: MiMo-V2-Flash's ``wq`` (100 MB a layer) crossed the HBM five times a
step where the product needs it once, and ``slice_bitcast_fusion`` + ``copy``
were 24% of its cell's chip time (PERF.md, PR 39). ``grouped_matmul`` reads its
expert stacks in place for the same reason (PR 25).

On a TPU ``stack_matmul`` is the Pallas kernel ``dstpu_stack_matmul``: column
tiles of the layer's ``[k, n]`` are fetched straight from the stack (the
layer's index sits in the weight block's index map), each multiplied with the
row tiles of ``x`` and accumulated in float32 by the MXU. Elsewhere, and as its
oracle, it is ``x @ stack[index]``.
"""

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.accelerator.device import on_tpu

# The kernel's name in a device trace, beside the other ``dstpu_*`` names.
STACK_MATMUL = "dstpu_stack_matmul"
# one weight block and one block of rows held in VMEM (twice each: the
# pipeline's two buffers), as grouped_matmul's
_BLOCK_BYTES = 4 << 20
_VMEM_LIMIT_BYTES = 48 << 20


class Stacked(NamedTuple):
    """Layer ``index`` (static) of parameters stacked ``[layers, k, n]``, not
    yet sliced: what ``stack_dot`` multiplies with in place. (A latent layer's
    ``wkv_b`` rides the same way to ``latent_pallas.latent_chunk``, whose
    kernel takes the index as a scalar: there a looped stack's traced layer
    will do.)"""
    stack: jax.Array
    index: int


def _col_tile(k: int, n: int, itemsize: int) -> int:
    """Columns of one weight block: the widest multiple of 128 that divides
    ``n`` and keeps ``[k, tn]`` in the block budget (all of a small ``n``)."""
    if n % 128:
        return n
    tn = min(n, max(128, (_BLOCK_BYTES // (k * itemsize)) // 128 * 128))
    while n % tn:
        tn -= 128
    return tn


def _row_tile(m: int, k: int, itemsize: int) -> int:
    """Rows of one block of ``x``: the most that divide ``m`` in whole sublane
    tiles and keep ``[tm, k]`` in twice the block budget (a decode step's rows
    are one block; a chunk step's 32 + 2 x 512 are two of 528)."""
    lo = 8 * max(1, 4 // itemsize)
    if m % lo:
        return m
    fit = max(lo, (2 * _BLOCK_BYTES) // (k * itemsize))
    return max(t for t in range(lo, m + 1, lo) if m % t == 0 and (t <= fit or t == lo))


def _kernel(x_ref, w_ref, o_ref):
    o_ref[...] = jnp.dot(x_ref[...], w_ref[...], preferred_element_type=jnp.float32).astype(o_ref.dtype)


def _stack_matmul_pallas(x, stack, index: int, interpret: bool):
    m, k = x.shape
    _, _, n = stack.shape
    tm, tn = _row_tile(m, k, x.dtype.itemsize), _col_tile(k, n, stack.dtype.itemsize)
    return pl.pallas_call(
        _kernel,
        # a weight block stays while the row blocks pass under it
        grid=(n // tn, m // tm),
        in_specs=[
            pl.BlockSpec((tm, k), lambda j, i: (i, 0)),
            pl.BlockSpec((None, k, tn), lambda j, i: (index, 0, j)),
        ],
        out_specs=pl.BlockSpec((tm, tn), lambda j, i: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
        name=STACK_MATMUL,
    )(x, stack)


def stack_matmul(x, stack, index: int, impl: Optional[str] = None):
    """``x [m, k] @ stack[index]`` for ``stack [layers, k, n]`` and a static
    ``index``. ``impl``: ``"kernel"`` (the Pallas kernel; on a TPU),
    ``"interpret"`` (the same kernel interpreted, for tests on the CPU) or
    ``"slice"`` (``x @ stack[index]``); None picks by the platform."""
    impl = impl or ("kernel" if on_tpu() else "slice")
    if impl == "slice":
        return x @ stack[index]
    return _stack_matmul_pallas(x, stack, int(index), impl == "interpret")


def stack_dot(x, w):
    """``x @ w`` for a weight ``[k, n]`` or a ``Stacked`` one; ``x`` is
    ``[..., k]``."""
    if not isinstance(w, Stacked):
        return x @ w
    lead = x.shape[:-1]
    out = stack_matmul(x.reshape(-1, x.shape[-1]), w.stack, w.index)
    return out.reshape(lead + out.shape[-1:])
