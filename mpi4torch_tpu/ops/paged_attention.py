"""Decode attention read straight out of a paged KV pool.

One query row per slot (the continuous-batching decode step of
:mod:`mpi4torch_tpu.serve`) against that slot's pages, found through the
block table.  Two realizations behind one signature, chosen like
:mod:`~mpi4torch_tpu.ops.flash` chooses — by backend and shapes alone:

* a Pallas TPU kernel (:data:`KERNEL_NAMES`): the table and the
  positions ride as scalar-prefetch arguments, the grid walks (slot,
  page), and the K/V index maps look the page up in the table — so a
  page travels HBM -> VMEM exactly once, and only if it lies between
  the slot's window start and its frontier.  Grid steps past the
  frontier repeat the last live page's index (the pipeline fetches
  nothing for an unchanged index) and compute nothing.  No array of
  the pool's or of a slot's ``max_seq`` extent is ever formed;
* the jnp path for every other platform and shape: the pages gathered
  into each slot's full extent by
  :func:`~mpi4torch_tpu.ops.ragged.block_gather` and attended by
  :func:`~mpi4torch_tpu.ops.flash.flash_block_attention` with
  ``impl="jnp"`` — the oracle the kernel is tested against
  (tests/test_paged_attention.py), and bit for bit the dense engine's
  read.

The pool keeps the serving layout ``(num_blocks, block_size, kv_heads,
head_dim)``: a page of all heads is one contiguous block, which the
kernel views as ``block_size * kv_heads`` rows of ``head_dim`` (row
``t * kv_heads + h``; the same bytes).  All of a slot's query heads
meet all of a page's rows in ONE product and a mask keeps, for each
query head, the rows of its own KV head — a GQA group shares the page
load, nothing is repeated over the group, and no per-head strided slice
of the page is needed.  The MXU's cost is set by the page's rows, not
by the few query rows, so the masked-out products are free.

Arithmetic as :func:`~mpi4torch_tpu.ops.flash._jnp_block` has it:
K and V read in the pool's dtype, scores, running max/sum and the P.V
accumulation in float32.  The softmax is online (page by page), so
against the jnp path the result is equal to rounding, not bitwise.

A **latent** pool (:func:`paged_latent_attention`) holds one row a
token for all heads, ``(num_blocks, block_size, 1, width)``: the row is
the key of every head, and its first ``v_width`` channels are the value
of every head (latent attention with the up-projections absorbed into
the query and the output, ``serve/kv.py``).  Its kernel is the one
above with one operand less: a page is fetched once and serves as key
and as value, and all heads share every row, so nothing is masked by
head.

Inference-only: no VJP.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .flash import NEG_BIG, _KV_VMEM_BUDGET, _STAT_LANES, _on_tpu, \
    dot_precision, flash_block_attention
from .ragged import block_gather

__all__ = ["paged_decode_attention", "paged_latent_attention",
           "latent_rows_attention", "uses_kernel", "KERNEL_NAMES"]

# Stable names of the Mosaic kernels: what a lowered program's
# ``kernel_name`` attributes and a profiler trace's kernel events are
# matched against (as ``flash.KERNEL_NAMES``): the K/V read, the latent
# read.
KERNEL_NAMES = ("mpi4torch_paged_attn", "mpi4torch_paged_latent_attn")


def _eligible(q, pool_k, v_width=None) -> bool:
    """Operands the kernel takes: ``head_dim`` a multiple of the lane
    width (128: the pool cannot be padded without copying it, and for a
    narrower head XLA lays the pool out so that the kernel's view of it
    is a copy of every leaf — compiled for the v5e at ``head_dim`` 64),
    ``block_size`` a multiple of the pool dtype's sublane tile (8 rows
    of 32 bits: 8 for float32, 16 for bfloat16), the query in the
    pool's dtype (a down-cast cache keeps the jnp path's promotion
    rules), and the staged pages — a K page and a V page, each
    double-buffered by the pipeline — within the budget ``ops.flash``
    gives its staged KV, so scores and accumulators still fit.  A latent
    pool (``v_width`` given) also needs its value width to be whole
    lanes, and one row a token."""
    if v_width is not None and (v_width % 128 != 0
                                or not 0 < v_width <= q.shape[-1]
                                or pool_k.shape[2] != 1):
        return False
    hd = q.shape[-1]
    bs, kvh = pool_k.shape[1], pool_k.shape[2]
    item = jnp.dtype(pool_k.dtype).itemsize
    if hd % 128 != 0 or q.dtype != pool_k.dtype or item not in (2, 4):
        return False
    if bs % (8 * (4 // item)) != 0:
        return False
    return 4 * bs * kvh * hd * item <= _KV_VMEM_BUDGET


def uses_kernel(q, pool_k, v_width=None) -> bool:
    """Whether ``impl="auto"`` takes the kernel for these operands (only
    their shapes and dtypes are read): the predicate a caller counts
    page reads by.  ``v_width`` is given for a latent pool
    (:func:`paged_latent_attention`), whose ``pool_k`` is its one
    leaf."""
    return _eligible(q, pool_k, v_width) and _on_tpu()


def _page_span(pos, bs: int, n_blk: int, window: int):
    """First and one-past-last page a query at ``pos`` attends (scalar
    int32 arithmetic, non-negative operands only so truncating division
    is floor).  ``pos < 0`` marks a slot that reads nothing; a position
    past the table's extent is held to the table (the index maps read
    the table at these pages)."""
    i32 = jnp.int32
    n_live = jnp.where(
        pos >= 0,
        jnp.minimum(jax.lax.div(jnp.maximum(pos, i32(0)), i32(bs)) + 1,
                    i32(n_blk)), i32(0))
    if not window:
        return jnp.zeros_like(n_live), n_live
    first = jax.lax.div(jnp.maximum(pos - (window - 1), i32(0)), i32(bs))
    return first, n_live


def _kernel(table_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
            m_ref, l_ref, acc_ref, *, bs: int, kvh: int, g: int,
            n_blk: int, window: int):
    from jax.experimental import pallas as pl

    f32, i32 = jnp.float32, jnp.int32
    s, j = pl.program_id(0), pl.program_id(1)
    pos = pos_ref[s]
    first, n_live = _page_span(pos, bs, n_blk, window)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, NEG_BIG, f32)
        l_ref[...] = jnp.zeros(l_ref.shape, f32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, f32)

    @pl.when((j >= first) & (j < n_live))
    def _page():
        q = q_ref[0]                                   # (heads, hd)
        k, v = k_ref[0], v_ref[0]                      # (bs * kvh, hd)
        heads, rows = q.shape[0], k.shape[0]
        prec = dot_precision(q.dtype)
        scale = 1.0 / jnp.sqrt(jnp.asarray(q.shape[1], f32))
        sc = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=f32, precision=prec) * scale
        # Row r of the page is position r // kvh of KV head r % kvh.
        col = jax.lax.broadcasted_iota(i32, (heads, rows), 1)
        row = jax.lax.broadcasted_iota(i32, (heads, rows), 0)
        kv_pos = j * bs + jax.lax.div(col, i32(kvh))
        mask = (jax.lax.rem(col, i32(kvh)) == jax.lax.div(row, i32(g))) \
            & (kv_pos <= pos)
        if window:
            mask &= (pos - kv_pos) < window
        # An unmapped page inside the frontier reads as zeros, as
        # block_gather hands it over (whatever page the clamped index
        # fetched is discarded, NaN and all).
        mapped = table_ref[s * n_blk + j] >= 0
        sc = jnp.where(mapped, sc, 0.0)
        sc = jnp.where(mask, sc, NEG_BIG)
        m_prev, l_prev = m_ref[:, :1], l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
        p = jnp.where(mask, jnp.exp(sc - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=f32, precision=prec)
        acc_ref[...] = acc_ref[...] * corr + jnp.where(mapped, pv, 0.0)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == n_blk - 1)
    def _finish():
        l = l_ref[:, :1]
        safe = jnp.where(l > 0, l, 1.0)
        o_ref[0] = jnp.where(l > 0, acc_ref[...] / safe,
                             0.0).astype(o_ref.dtype)


def _pallas_paged(q, pool_k, pool_v, table, pos, window: int,
                  interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    slots, heads, hd = q.shape
    nb, bs, kvh, _ = pool_k.shape
    n_blk = table.shape[1]
    g = heads // kvh

    def page_index(s, j, table_ref, pos_ref):
        # The page the table names for the nearest live step: steps
        # before the window and past the frontier repeat a live page's
        # index, so nothing is fetched for them.
        first, n_live = _page_span(pos_ref[s], bs, n_blk, window)
        jj = jnp.clip(j, first, jnp.maximum(n_live - 1, 0))
        return jnp.maximum(table_ref[s * n_blk + jj], 0), 0, 0

    def slot_index(s, j, table_ref, pos_ref):
        return s, 0, 0

    vmem = functools.partial(pl.BlockSpec, memory_space=pltpu.VMEM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(slots, n_blk),
        in_specs=[vmem((1, heads, hd), slot_index),
                  vmem((1, bs * kvh, hd), page_index),
                  vmem((1, bs * kvh, hd), page_index)],
        out_specs=vmem((1, heads, hd), slot_index),
        scratch_shapes=[pltpu.VMEM((heads, _STAT_LANES), jnp.float32),
                        pltpu.VMEM((heads, _STAT_LANES), jnp.float32),
                        pltpu.VMEM((heads, hd), jnp.float32)])
    return pl.pallas_call(
        functools.partial(_kernel, bs=bs, kvh=kvh, g=g, n_blk=n_blk,
                          window=window),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name=KERNEL_NAMES[0],
    )(table.reshape(-1), pos, q,
      pool_k.reshape(nb, bs * kvh, hd), pool_v.reshape(nb, bs * kvh, hd))


def paged_decode_attention(q, pool_k, pool_v, table, pos, *,
                           window: int = 0, active=None,
                           impl: str = "auto"):
    """Causal attention of one query row per slot over the slot's pages.

    ``q``: ``(slots, heads, head_dim)``, slot ``s`` sitting at position
    ``pos[s]`` and attending positions ``0..pos[s]`` (its own row
    included; the last ``window`` of them when ``window > 0``).
    ``pool_k`` / ``pool_v``: ``(num_blocks, block_size, kv_heads,
    head_dim)``, ``heads`` a multiple of ``kv_heads`` (query head ``h``
    reads KV head ``h // (heads // kv_heads)``).  ``table``: ``(slots,
    n_blk)`` int page ids, position ``t`` of slot ``s`` living at
    ``pool[table[s, t // block_size], t % block_size]``; a negative
    entry is an unmapped page and reads as zeros.  ``active`` (``(slots,)``
    bool/int, optional): a slot marked inactive reads no page and
    returns zeros.  Returns ``(slots, heads, head_dim)`` in ``q``'s
    dtype.

    Rows behind the frontier are masked, not skipped, where they share
    a page with live rows: whatever they hold must be finite.  Pages
    wholly beyond the frontier (or wholly behind the window), and pages
    no table row of a live slot names, are never read on the kernel
    path.

    ``impl``: ``"auto"`` (the kernel on a TPU for eligible shapes, else
    jnp), ``"pallas"`` (forced; interpreted off the TPU — for tests),
    ``"jnp"``."""
    if impl not in ("auto", "pallas", "jnp"):
        raise ValueError(f"unknown impl {impl!r}")
    if q.ndim != 3 or pool_k.ndim != 4 or pool_k.shape != pool_v.shape \
            or q.shape[2] != pool_k.shape[3]:
        raise ValueError(
            f"q{q.shape} must be (slots, heads, head_dim) and the pools "
            f"k{pool_k.shape}/v{pool_v.shape} one shape (num_blocks, "
            "block_size, kv_heads, head_dim) of the same head_dim")
    if q.shape[1] % pool_k.shape[2] != 0:
        raise ValueError(
            f"query heads ({q.shape[1]}) must be a multiple of KV heads "
            f"({pool_k.shape[2]})")
    if window < 0:
        raise ValueError(f"window must be >= 0, got {window}")
    table = jnp.asarray(table, jnp.int32)
    pos = jnp.asarray(pos, jnp.int32)
    if table.ndim != 2 or table.shape[0] != q.shape[0] \
            or pos.shape != (q.shape[0],):
        raise ValueError(
            f"table{table.shape} must be (slots, n_blk) and pos{pos.shape} "
            f"(slots,) for {q.shape[0]} slots")
    live = None if active is None else jnp.asarray(active).astype(bool)
    if impl == "pallas" and not _eligible(q, pool_k):
        raise ValueError(
            f"impl='pallas' requires kernel-eligible operands (head_dim a "
            f"multiple of 128, block_size of the sublane tile, q in the "
            f"pool's dtype, a page pair within the VMEM budget); got "
            f"q{q.shape} {q.dtype} pool{pool_k.shape} {pool_k.dtype}")
    if impl == "pallas" or (impl == "auto" and uses_kernel(q, pool_k)):
        if live is not None:
            pos = jnp.where(live, pos, -1)
        return _pallas_paged(q, pool_k, pool_v, table, pos, window,
                             interpret=not _on_tpu())
    o, _ = flash_block_attention(
        q[:, None], block_gather(pool_k, table), block_gather(pool_v, table),
        causal=True, q_offset=pos, kv_offset=0, window=window, impl="jnp")
    o = o[:, 0]
    if live is not None:
        o = jnp.where(live[:, None, None], o, jnp.zeros((), o.dtype))
    return o


# ---------------------------------------------------------------------------
# The latent read
# ---------------------------------------------------------------------------


def _latent_kernel(table_ref, pos_ref, q_ref, c_ref, o_ref, m_ref, l_ref,
                   acc_ref, *, bs: int, n_blk: int, v_width: int,
                   scale: float):
    from jax.experimental import pallas as pl

    f32, i32 = jnp.float32, jnp.int32
    s, j = pl.program_id(0), pl.program_id(1)
    pos = pos_ref[s]
    _, n_live = _page_span(pos, bs, n_blk, 0)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, NEG_BIG, f32)
        l_ref[...] = jnp.zeros(l_ref.shape, f32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, f32)

    @pl.when(j < n_live)
    def _page():
        q, c = q_ref[0], c_ref[0]                # (heads, w), (bs, w)
        prec = dot_precision(q.dtype)
        sc = jax.lax.dot_general(
            q, c, (((1,), (1,)), ((), ())),
            preferred_element_type=f32, precision=prec) * scale
        kv_pos = j * bs + jax.lax.broadcasted_iota(i32, sc.shape, 1)
        mask = kv_pos <= pos
        # An unmapped page inside the frontier reads as zeros, as the
        # K/V kernel has it.
        mapped = table_ref[s * n_blk + j] >= 0
        sc = jnp.where(mapped, sc, 0.0)
        sc = jnp.where(mask, sc, NEG_BIG)
        m_prev, l_prev = m_ref[:, :1], l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
        p = jnp.where(mask, jnp.exp(sc - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        # The value of a row is the head of the row itself.
        pv = jax.lax.dot_general(
            p.astype(c.dtype), c[:, :v_width], (((1,), (0,)), ((), ())),
            preferred_element_type=f32, precision=prec)
        acc_ref[...] = acc_ref[...] * corr + jnp.where(mapped, pv, 0.0)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == n_blk - 1)
    def _finish():
        l = l_ref[:, :1]
        safe = jnp.where(l > 0, l, 1.0)
        o_ref[0] = jnp.where(l > 0, acc_ref[...] / safe,
                             0.0).astype(o_ref.dtype)


def _pallas_latent(q, pool_c, table, pos, v_width: int, scale: float,
                   interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    slots, heads, w = q.shape
    nb, bs = pool_c.shape[0], pool_c.shape[1]
    n_blk = table.shape[1]

    def page_index(s, j, table_ref, pos_ref):
        # Steps past the frontier repeat the last live page's index:
        # nothing is fetched for them.
        _, n_live = _page_span(pos_ref[s], bs, n_blk, 0)
        jj = jnp.clip(j, 0, jnp.maximum(n_live - 1, 0))
        return jnp.maximum(table_ref[s * n_blk + jj], 0), 0, 0

    def slot_index(s, j, table_ref, pos_ref):
        return s, 0, 0

    vmem = functools.partial(pl.BlockSpec, memory_space=pltpu.VMEM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(slots, n_blk),
        in_specs=[vmem((1, heads, w), slot_index),
                  vmem((1, bs, w), page_index)],
        out_specs=vmem((1, heads, v_width), slot_index),
        scratch_shapes=[pltpu.VMEM((heads, _STAT_LANES), jnp.float32),
                        pltpu.VMEM((heads, _STAT_LANES), jnp.float32),
                        pltpu.VMEM((heads, v_width), jnp.float32)])
    return pl.pallas_call(
        functools.partial(_latent_kernel, bs=bs, n_blk=n_blk,
                          v_width=v_width, scale=scale),
        out_shape=jax.ShapeDtypeStruct((slots, heads, v_width), q.dtype),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name=KERNEL_NAMES[1],
    )(table.reshape(-1), pos, q, pool_c.reshape(nb, bs, w))


def latent_rows_attention(q, rows, pos, *, v_width: int, scale: float,
                          active=None):
    """The latent read in plain jnp over rows that are already laid out
    a slot at a time: ``q`` ``(slots, heads, width)`` at positions
    ``pos`` against ``rows`` ``(slots, n, width)``, row ``t`` of a slot
    being its position ``t``; scores ``scale * q . row`` over rows
    ``0..pos``, values the rows' first ``v_width`` channels.  Scores,
    softmax and the weighted sum in at least float32, as
    :func:`~mpi4torch_tpu.ops.flash._jnp_block` has them.  The dense
    slot cache's read, and (behind a gather) the paged pool's oracle.
    Returns ``(slots, heads, v_width)`` in ``q``'s dtype."""
    ct = jnp.promote_types(q.dtype, jnp.float32)
    prec = dot_precision(q.dtype)
    sc = jnp.einsum("shw,snw->shn", q.astype(ct), rows.astype(ct),
                    precision=prec) * jnp.asarray(scale, ct)
    mask = (jnp.arange(rows.shape[1], dtype=jnp.int32)[None, :]
            <= pos[:, None])[:, None, :]
    sc = jnp.where(mask, sc, NEG_BIG)
    p = jnp.where(mask, jnp.exp(sc - jnp.max(sc, axis=-1, keepdims=True)),
                  0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    acc = jnp.einsum("shn,snv->shv", p, rows[..., :v_width].astype(ct),
                     precision=prec)
    o = jnp.where(l > 0, acc / jnp.where(l > 0, l, 1.0), 0.0)
    if active is not None:
        o = jnp.where(active[:, None, None], o, 0.0)
    return o.astype(q.dtype)


def paged_latent_attention(q, pool_c, table, pos, *, v_width: int,
                           scale: float, active=None, impl: str = "auto"):
    """Causal attention of one query row per slot over the slot's pages
    of a LATENT pool: every head reads the same rows.

    ``q``: ``(slots, heads, width)``, the up-projection of the keys
    already absorbed into it; ``pool_c``: ``(num_blocks, block_size, 1,
    width)``; ``table``, ``pos``, ``active``, ``impl`` as
    :func:`paged_decode_attention` has them.  Head ``h`` of slot ``s``
    weighs rows ``0..pos[s]`` by ``softmax(scale * q[s, h] . row)`` and
    returns the weighted sum of the rows' first ``v_width`` channels:
    ``(slots, heads, v_width)`` in ``q``'s dtype, for the caller to take
    through the values' up-projection.

    The kernel (on a TPU: ``width`` and ``v_width`` multiples of 128,
    ``block_size`` of the sublane tile, ``q`` in the pool's dtype)
    fetches a page once for both of its uses, and only pages up to each
    slot's frontier; everywhere else the pages are gathered into each
    slot's full extent and attended by :func:`latent_rows_attention`,
    the oracle the kernel is tested against."""
    if impl not in ("auto", "pallas", "jnp"):
        raise ValueError(f"unknown impl {impl!r}")
    if q.ndim != 3 or pool_c.ndim != 4 or pool_c.shape[2] != 1 \
            or q.shape[2] != pool_c.shape[3]:
        raise ValueError(
            f"q{q.shape} must be (slots, heads, width) and the latent "
            f"pool{pool_c.shape} (num_blocks, block_size, 1, width) of "
            "the same width")
    if not 0 < v_width <= q.shape[2]:
        raise ValueError(
            f"v_width={v_width} must lie in (0, width={q.shape[2]}]")
    table = jnp.asarray(table, jnp.int32)
    pos = jnp.asarray(pos, jnp.int32)
    if table.ndim != 2 or table.shape[0] != q.shape[0] \
            or pos.shape != (q.shape[0],):
        raise ValueError(
            f"table{table.shape} must be (slots, n_blk) and pos{pos.shape} "
            f"(slots,) for {q.shape[0]} slots")
    live = None if active is None else jnp.asarray(active).astype(bool)
    if impl == "pallas" and not _eligible(q, pool_c, v_width):
        raise ValueError(
            f"impl='pallas' requires kernel-eligible operands (width and "
            f"v_width multiples of 128, block_size of the sublane tile, "
            f"q in the pool's dtype); got q{q.shape} {q.dtype} "
            f"pool{pool_c.shape} {pool_c.dtype} v_width={v_width}")
    if impl == "pallas" or (impl == "auto"
                            and uses_kernel(q, pool_c, v_width)):
        if live is not None:
            pos = jnp.where(live, pos, -1)
        return _pallas_latent(q, pool_c, table, pos, v_width, float(scale),
                              interpret=not _on_tpu())
    return latent_rows_attention(
        q, block_gather(pool_c, table)[:, :, 0], pos, v_width=v_width,
        scale=scale, active=live)
