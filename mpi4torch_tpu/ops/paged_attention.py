"""Decode attention read straight out of a paged KV pool.

One query row per slot (the continuous-batching decode step of
:mod:`mpi4torch_tpu.serve`) against that slot's pages, found through the
block table.  Two realizations behind one signature, chosen like
:mod:`~mpi4torch_tpu.ops.flash` chooses — by backend and shapes alone:

* a Pallas TPU kernel (:data:`KERNEL_NAMES`): the pages' ids (the
  table, resolved once a call in plain XLA) and the positions ride as
  scalar-prefetch arguments, the grid walks (slot, group of pages), and
  the K/V index maps look each page of the group up there — so a page
  travels HBM -> VMEM exactly once, and only if it lies between the
  slot's window start and its frontier.
  A grid step takes several pages (:func:`read_grid`: as many as
  divide the table's width and fit the staging budget), each through an
  operand of its own, and folds them into the running softmax in ONE
  update.  An operand whose page lies outside the span repeats the
  nearest live page OF ITS OWN (the pipeline fetches nothing for an
  unchanged index), and a step none of whose pages is live computes
  nothing.  No array of the pool's or of a slot's ``max_seq`` extent is
  ever formed;
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
accumulation in float32.  The softmax is online (a grid step's pages
at a time), so against the jnp path the result is equal to rounding,
not bitwise.

A **latent** pool (:func:`paged_latent_attention`) holds one row a
token for all heads, ``(num_blocks, block_size, 1, width)``: the row is
the key of every head, and its first ``v_width`` channels are the value
of every head (latent attention with the up-projections absorbed into
the query and the output, ``serve/kv.py``).  Its kernel is the one
above with one operand less: a page is fetched once and serves as key
and as value, and all heads share every row, so nothing is masked by
head.

Two more reads serve SPARSE latent attention (a latent layer with an
indexer, ``models/transformer.py``): :func:`paged_index_scores` scores
every cached position of a slot from a second, narrow pool of index keys
(``(num_blocks, block_size, 1, head_dim)``, one row a token), page by
page up to the frontier; and :func:`paged_sparse_latent_attention` reads
of the latent pool only the rows a selection names, found by page and
offset through the block table, and attends those.

Inference-only: no VJP.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from .flash import NEG_BIG, _KV_VMEM_BUDGET, _STAT_LANES, _on_tpu, \
    dot_precision, flash_block_attention
from .ragged import block_gather

__all__ = ["paged_decode_attention", "paged_latent_attention",
           "latent_rows_attention", "paged_index_scores",
           "index_scores", "index_rows_scores",
           "paged_sparse_latent_attention",
           "sparse_rows_gather", "uses_kernel", "uses_index_kernel",
           "read_grid", "KERNEL_NAMES"]

# Stable names of the Mosaic kernels: what a lowered program's
# ``kernel_name`` attributes and a profiler trace's kernel events are
# matched against (as ``flash.KERNEL_NAMES``): the K/V read, the latent
# read, the index scoring, the latent read over selected rows.
KERNEL_NAMES = ("mpi4torch_paged_attn", "mpi4torch_paged_latent_attn",
                "mpi4torch_paged_index_score",
                "mpi4torch_paged_sparse_latent_attn")
# The scope the selected rows' gather runs under, left to XLA: its
# instructions carry the name in their ``op_name``, and their time is
# the sparse read's.
SPARSE_GATHER_SCOPE = "mpi4torch.paged_sparse_gather"


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
    """First and one-past-last page a query at ``pos`` attends (int32
    arithmetic on a kernel's scalar or on every slot's at once,
    non-negative operands only so truncating division is floor).
    ``pos < 0`` marks a slot that reads nothing; a position past the
    table's extent is held to the table."""
    i32 = jnp.int32
    n_live = jnp.where(
        pos >= 0,
        jnp.minimum(jnp.maximum(pos, i32(0)) // i32(bs) + 1, i32(n_blk)),
        i32(0))
    if not window:
        return jnp.zeros_like(n_live), n_live
    return jnp.maximum(pos - (window - 1), i32(0)) // i32(bs), n_live


def _pages_a_step(n_blk: int, page_bytes: int) -> int:
    """Pages one grid step of a paged read takes, each through an
    operand of its own, so that they share the step's fixed cost: the
    largest of 8, 4, 2, 1 that divides the table's width and keeps that
    many double-buffered pages (``page_bytes`` each: a K page and a V
    page where the read stages both) within the budget ``_eligible``
    holds a single page to."""
    return next((g for g in (8, 4, 2) if n_blk % g == 0
                 and 2 * g * page_bytes <= _KV_VMEM_BUDGET), 1)


def read_grid(slots: int, n_blk: int, *pools) -> tuple:
    """The grid a paged read's kernel walks over a table of ``(slots,
    n_blk)``: ``(slots, n_blk // pages a step)``.  ``pools``: the pool
    leaves the read stages a page of (K and V; the one latent pool; the
    index keys); only shapes and dtypes are read.  What the kernels give
    as ``grid=``, and what a caller counts grid steps by."""
    page_bytes = sum(math.prod(p.shape[1:]) * jnp.dtype(p.dtype).itemsize
                     for p in pools)
    return slots, n_blk // _pages_a_step(n_blk, page_bytes)


def _page_ids(table, pos, bs: int, window: int, group: int):
    """The pool page each operand of each grid step fetches, ``(slots *
    n_blk,)`` int32 for the kernels' scalar prefetch: entry ``s * n_blk
    + j * group + g`` is what operand ``g`` holds at grid step ``j`` of
    slot ``s``.  Inside the slot's span that is the table's own entry
    (``-1``, an unmapped page, stays: the index map holds it to page 0
    and the kernel reads zeros).  Outside it the operand repeats the
    nearest live page OF ITS OWN (the pages ``g`` modulo ``group``), so
    every page it names is one it folds and nothing is fetched for a
    dead step; where the span holds none of its own, the pool's page 0,
    which it keeps until a slot gives it one.  Worked out here, in
    plain XLA and once for all of a decode step's layers (they share
    the table and the positions), and not by the index maps: a grid
    step pays the scalar core for every operand's look-up, live or
    dead."""
    i32 = jnp.int32
    slots, n_blk = table.shape
    first, n_live = _page_span(pos, bs, n_blk, window)
    by_step = table.reshape(slots, n_blk // group, group)
    step = jnp.arange(n_blk // group, dtype=i32)[None, :, None]
    g = jnp.arange(group, dtype=i32)[None, :]
    # The grid step of an operand's first page at or behind `first`,
    # and of its last below `n_live` (none: lo > hi).
    lo = (first[:, None] + (group - 1 - g)) // group
    hi = (n_live[:, None] + (group - 1 - g)) // group - 1
    # Its entry at a step, by comparison against the steps: a gather of
    # a few scalars costs a TPU more than the whole row's comparison.
    at = lambda k: jnp.sum(jnp.where(step == k[:, None, :], by_step, 0),
                           axis=1, keepdims=True)
    ids = jnp.where(step > hi[:, None, :], at(hi), by_step)
    if window:
        ids = jnp.where(step < lo[:, None, :], at(lo), ids)
    return jnp.where((lo <= hi)[:, None, :], ids, 0).reshape(-1)


def _page_indices(group: int, n_blk: int):
    """The index maps of a grid step's ``group`` page operands over
    :func:`_page_ids`."""
    def index_of(g: int):
        def index(s, j, ids_ref, pos_ref):
            return jnp.maximum(ids_ref[s * n_blk + j * group + g], 0), 0, 0
        return index

    return [index_of(g) for g in range(group)]


def _slot_index(s, j, ids_ref, pos_ref):
    return s, 0, 0


def _start(j, m_ref, l_ref, acc_ref):
    from jax.experimental import pallas as pl

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, NEG_BIG, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)


def _fold_live_pages(fold, page0, group: int, first, n_live):
    """Run ``fold(n)``, the update over a grid step's first ``n`` pages,
    where the step (pages ``page0 .. page0 + group``) holds a live page:
    over all of them, or over the first half where no live page lies
    behind it (a frontier just past a step's start, as behind a prompt
    of whole steps, would otherwise pay for a whole step of products)."""
    from jax.experimental import pallas as pl

    live = (page0 + group > first) & (page0 < n_live)
    half = group // 2
    if not half:
        pl.when(live)(lambda: fold(group))
        return
    pl.when(live & (page0 + half >= n_live))(lambda: fold(half))
    pl.when(live & (page0 + half < n_live))(lambda: fold(group))


def _as_read(sc, v, live, mapped):
    """A page's scores and values as the fold takes them: an unmapped
    page inside the frontier reads as zeros, as block_gather hands it
    over, and a page outside the span (its scores are masked, its
    operand holds whatever it last fetched) brings zero values, so that
    nothing it holds, NaN and all, meets a weight."""
    return (jnp.where(mapped, sc, 0.0),
            jnp.where(live & mapped, v, jnp.zeros((), v.dtype)))


def _side_by_side(scs, vals):
    """A grid step's pages as ONE block of scores and one of values, in
    page order: they enter the running softmax in one update.  A page
    folded alone pays the whole chain product - maximum - exponential -
    product - rescale before the next may start, and that chain, not
    the page's bytes or the grid step, is most of what a live page
    costs (0.76 us for a page whose bytes need 0.20, PERF.md)."""
    if len(scs) == 1:
        return scs[0], vals[0]
    return jnp.concatenate(scs, axis=1), jnp.concatenate(vals, axis=0)


def _fold(sc, mask, v, m_ref, l_ref, acc_ref, prec):
    """Scores ``sc`` (float32, ``mask`` their attended pairs) and the
    values ``v`` of their rows into the running max, sum and
    accumulator."""
    sc = jnp.where(mask, sc, NEG_BIG)
    m_prev, l_prev = m_ref[:, :1], l_ref[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
    p = jnp.where(mask, jnp.exp(sc - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
    pv = jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32, precision=prec)
    acc_ref[...] = acc_ref[...] * corr + pv
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)


def _finish(j, steps: int, o_ref, l_ref, acc_ref):
    from jax.experimental import pallas as pl

    @pl.when(j == steps - 1)
    def _out():
        l = l_ref[:, :1]
        safe = jnp.where(l > 0, l, 1.0)
        o_ref[0] = jnp.where(l > 0, acc_ref[...] / safe,
                             0.0).astype(o_ref.dtype)


def _kernel(ids_ref, pos_ref, q_ref, *refs, bs: int, kvh: int, g: int,
            n_blk: int, window: int, group: int):
    from jax.experimental import pallas as pl

    f32, i32 = jnp.float32, jnp.int32
    s, j = pl.program_id(0), pl.program_id(1)
    k_refs, v_refs = refs[:group], refs[group:2 * group]
    o_ref, m_ref, l_ref, acc_ref = refs[2 * group:]
    pos = pos_ref[s]
    first, n_live = _page_span(pos, bs, n_blk, window)
    page0 = j * group
    _start(j, m_ref, l_ref, acc_ref)

    def fold(pages: int):
        q = q_ref[0]                                       # (heads, hd)
        prec = dot_precision(q.dtype)
        scale = 1.0 / jnp.sqrt(jnp.asarray(q.shape[1], f32))
        scs, vals = [], []
        for n in range(pages):
            sc = jax.lax.dot_general(
                q, k_refs[n][0], (((1,), (1,)), ((), ())),  # (bs * kvh, hd)
                preferred_element_type=f32, precision=prec) * scale
            sc, v = _as_read(
                sc, v_refs[n][0],
                (page0 + n >= first) & (page0 + n < n_live),
                ids_ref[s * n_blk + page0 + n] >= 0)
            scs.append(sc)
            vals.append(v)
        sc, v = _side_by_side(scs, vals)
        # Column c is row c % (bs * kvh) of page page0 + c // (bs * kvh),
        # and row r of a page is position r // kvh of KV head r % kvh
        # (bs * kvh is a multiple of kvh).
        col = jax.lax.broadcasted_iota(i32, sc.shape, 1)
        row = jax.lax.broadcasted_iota(i32, sc.shape, 0)
        kv_pos = page0 * bs + jax.lax.div(col, i32(kvh))
        mask = (jax.lax.rem(col, i32(kvh)) == jax.lax.div(row, i32(g))) \
            & (kv_pos <= pos)
        if window:
            mask &= (pos - kv_pos) < window
        _fold(sc, mask, v, m_ref, l_ref, acc_ref, prec)

    _fold_live_pages(fold, page0, group, first, n_live)
    _finish(j, n_blk // group, o_ref, l_ref, acc_ref)


def _pallas_paged(q, pool_k, pool_v, table, pos, window: int,
                  interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    slots, heads, hd = q.shape
    nb, bs, kvh, _ = pool_k.shape
    n_blk = table.shape[1]
    grid = read_grid(slots, n_blk, pool_k, pool_v)
    group = n_blk // grid[1]
    vmem = functools.partial(pl.BlockSpec, memory_space=pltpu.VMEM)
    pages = [vmem((1, bs * kvh, hd), index)
             for index in _page_indices(group, n_blk)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[vmem((1, heads, hd), _slot_index)] + pages + pages,
        out_specs=vmem((1, heads, hd), _slot_index),
        scratch_shapes=[pltpu.VMEM((heads, _STAT_LANES), jnp.float32),
                        pltpu.VMEM((heads, _STAT_LANES), jnp.float32),
                        pltpu.VMEM((heads, hd), jnp.float32)])
    return pl.pallas_call(
        functools.partial(_kernel, bs=bs, kvh=kvh, g=heads // kvh,
                          n_blk=n_blk, window=window, group=group),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name=KERNEL_NAMES[0],
    )(_page_ids(table, pos, bs, window, group), pos, q,
      *[pool_k.reshape(nb, bs * kvh, hd)] * group,
      *[pool_v.reshape(nb, bs * kvh, hd)] * group)


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


def _latent_kernel(ids_ref, pos_ref, q_ref, *refs, bs: int, n_blk: int,
                   v_width: int, scale: float, group: int):
    from jax.experimental import pallas as pl

    f32, i32 = jnp.float32, jnp.int32
    s, j = pl.program_id(0), pl.program_id(1)
    c_refs = refs[:group]
    o_ref, m_ref, l_ref, acc_ref = refs[group:]
    pos = pos_ref[s]
    _, n_live = _page_span(pos, bs, n_blk, 0)
    page0 = j * group
    _start(j, m_ref, l_ref, acc_ref)

    def fold(pages: int):
        q = q_ref[0]                                       # (heads, w)
        prec = dot_precision(q.dtype)
        scs, vals = [], []
        for n in range(pages):
            c = c_refs[n][0]                               # (bs, w)
            sc = jax.lax.dot_general(
                q, c, (((1,), (1,)), ((), ())),
                preferred_element_type=f32, precision=prec) * scale
            # The value of a row is the head of the row itself.
            sc, v = _as_read(sc, c[:, :v_width], page0 + n < n_live,
                             ids_ref[s * n_blk + page0 + n] >= 0)
            scs.append(sc)
            vals.append(v)
        sc, v = _side_by_side(scs, vals)
        kv_pos = page0 * bs + jax.lax.broadcasted_iota(i32, sc.shape, 1)
        _fold(sc, kv_pos <= pos, v, m_ref, l_ref, acc_ref, prec)

    _fold_live_pages(fold, page0, group, 0, n_live)
    _finish(j, n_blk // group, o_ref, l_ref, acc_ref)


def _pallas_latent(q, pool_c, table, pos, v_width: int, scale: float,
                   interpret: bool, name: str = KERNEL_NAMES[1]):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    slots, heads, w = q.shape
    nb, bs = pool_c.shape[0], pool_c.shape[1]
    n_blk = table.shape[1]
    grid = read_grid(slots, n_blk, pool_c)
    group = n_blk // grid[1]
    vmem = functools.partial(pl.BlockSpec, memory_space=pltpu.VMEM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[vmem((1, heads, w), _slot_index)]
        + [vmem((1, bs, w), index)
           for index in _page_indices(group, n_blk)],
        out_specs=vmem((1, heads, v_width), _slot_index),
        scratch_shapes=[pltpu.VMEM((heads, _STAT_LANES), jnp.float32),
                        pltpu.VMEM((heads, _STAT_LANES), jnp.float32),
                        pltpu.VMEM((heads, v_width), jnp.float32)])
    return pl.pallas_call(
        functools.partial(_latent_kernel, bs=bs, n_blk=n_blk,
                          v_width=v_width, scale=scale, group=group),
        out_shape=jax.ShapeDtypeStruct((slots, heads, v_width), q.dtype),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name=name,
    )(_page_ids(table, pos, bs, 0, group), pos, q,
      *[pool_c.reshape(nb, bs, w)] * group)


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


# ---------------------------------------------------------------------------
# Sparse latent attention: the index scoring and the read of selected rows
# ---------------------------------------------------------------------------


def _index_eligible(q_i, pool_k) -> bool:
    """Operands the scoring kernel takes: index keys of whole lanes, one
    row a token, ``block_size`` of the pool dtype's sublane tile and of
    whole lanes (a page's scores are one row of the result), the queries
    in the pool's dtype."""
    hd, bs = q_i.shape[-1], pool_k.shape[1]
    item = jnp.dtype(pool_k.dtype).itemsize
    return (hd % 128 == 0 and bs % 128 == 0 and pool_k.shape[2] == 1
            and q_i.dtype == pool_k.dtype and item in (2, 4))


def uses_index_kernel(q_i, pool_k) -> bool:
    """Whether ``impl="auto"`` of :func:`paged_index_scores` takes the
    kernel for these operands (shapes and dtypes only)."""
    return _index_eligible(q_i, pool_k) and _on_tpu()


def _index_kernel(ids_ref, pos_ref, q_ref, w_ref, *refs, bs: int,
                  n_blk: int, group: int):
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    s, j = pl.program_id(0), pl.program_id(1)
    k_refs, o_ref = refs[:group], refs[group]
    _, n_live = _page_span(pos_ref[s], bs, n_blk, 0)
    q, w = q_ref[0], w_ref[0]              # (heads, hd), (heads, bs) f32
    prec = dot_precision(q.dtype)
    for g in range(group):
        page = j * group + g

        @pl.when(page < n_live)
        def _page(g=g, page=page):
            sc = jax.lax.dot_general(
                q, k_refs[g][0], (((1,), (1,)), ((), ())),
                preferred_element_type=f32, precision=prec)
            row = jnp.sum(jnp.maximum(sc, 0.0) * w, axis=0, keepdims=True)
            # An unmapped page inside the frontier scores as zero rows.
            mapped = ids_ref[s * n_blk + page] >= 0
            o_ref[0, 0, pl.ds(g, 1), :] = jnp.where(mapped, row, 0.0)

        @pl.when(page >= n_live)
        def _dead(g=g):
            o_ref[0, 0, pl.ds(g, 1), :] = jnp.zeros((1, bs), f32)


def _pallas_index(q_i, w, pool_k, table, pos, interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    slots, heads, hd = q_i.shape
    nb, bs = pool_k.shape[0], pool_k.shape[1]
    n_blk = table.shape[1]
    grid = read_grid(slots, n_blk, pool_k)
    group = n_blk // grid[1]
    vmem = functools.partial(pl.BlockSpec, memory_space=pltpu.VMEM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[vmem((1, heads, hd), _slot_index),
                  vmem((1, heads, bs), _slot_index)]
        + [vmem((1, bs, hd), index)
           for index in _page_indices(group, n_blk)],
        out_specs=vmem((1, 1, group, bs),
                       lambda s, j, ids_ref, pos_ref: (s, j, 0, 0)))
    pages = pool_k.reshape(nb, bs, hd)
    out = pl.pallas_call(
        functools.partial(_index_kernel, bs=bs, n_blk=n_blk, group=group),
        out_shape=jax.ShapeDtypeStruct(
            (slots, n_blk // group, group, bs), jnp.float32),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name=KERNEL_NAMES[2],
    )(_page_ids(table, pos, bs, 0, group), pos, q_i,
      jnp.broadcast_to(w.astype(jnp.float32)[:, :, None],
                       (slots, heads, bs)), *([pages] * group))
    return out.reshape(slots, n_blk * bs)


def index_scores(q_i, k_i, w):
    """The index score of every (query, cached position) pair in plain
    jnp, float32 at the least: ``q_i`` ``(..., q, heads, head_dim)``
    against ``k_i`` ``(..., n, head_dim)`` with ``w`` ``(..., q, heads)``
    gives ``sum_j w_j relu(q_j . k)``, ``(..., q, n)``.  The ONE place
    the score is written out: a prefill's block of queries against its
    pass's keys, and (:func:`index_rows_scores`) a decode step's."""
    ct = jnp.promote_types(q_i.dtype, jnp.float32)
    sc = jnp.einsum("...qjc,...nc->...qjn", q_i.astype(ct), k_i.astype(ct),
                    precision=dot_precision(q_i.dtype))
    return jnp.einsum("...qjn,...qj->...qn", jnp.maximum(sc, 0), w.astype(ct))


def index_rows_scores(q_i, k_rows, w):
    """:func:`index_scores` over index keys laid out a slot at a time,
    one query a slot: ``q_i`` ``(slots, heads, head_dim)`` against
    ``k_rows`` ``(slots, n, head_dim)`` with ``w`` ``(slots, heads)``
    gives ``(slots, n)``.  The dense slot cache's scoring, and (behind a
    gather) the paged pool's oracle."""
    return index_scores(q_i[:, None], k_rows, w[:, None])[:, 0]


def paged_index_scores(q_i, w, pool_k, table, pos, *, active=None,
                       impl: str = "auto"):
    """The index score of every cached position of every slot, from a
    pool of index keys: ``q_i`` ``(slots, heads, head_dim)`` one slot's
    index queries, ``w`` ``(slots, heads)`` their float32 weights,
    ``pool_k`` ``(num_blocks, block_size, 1, head_dim)``; ``table``,
    ``pos``, ``active``, ``impl`` as :func:`paged_decode_attention` has
    them.  Returns ``(slots, n_blk * block_size)`` float32: position
    ``t`` of slot ``s`` scores ``sum_j w[s, j] relu(q_i[s, j] . key_t)``.
    Only positions ``0..pos[s]`` of an active slot mean anything (the
    caller's mask): the kernel fetches no page beyond a frontier and
    writes zeros there, the jnp path scores whatever the gather found.

    The kernel (on a TPU: ``head_dim`` and ``block_size`` multiples of
    128, ``q_i`` in the pool's dtype) takes several pages a grid step,
    each fetched once; everywhere else the pages are gathered into each
    slot's full extent and scored by :func:`index_rows_scores`, its
    oracle."""
    if impl not in ("auto", "pallas", "jnp"):
        raise ValueError(f"unknown impl {impl!r}")
    if q_i.ndim != 3 or pool_k.ndim != 4 or pool_k.shape[2] != 1 \
            or q_i.shape[2] != pool_k.shape[3] \
            or w.shape != q_i.shape[:2]:
        raise ValueError(
            f"q_i{q_i.shape} must be (slots, heads, head_dim), w{w.shape} "
            f"(slots, heads) and the index-key pool{pool_k.shape} "
            "(num_blocks, block_size, 1, head_dim) of the same head_dim")
    table = jnp.asarray(table, jnp.int32)
    pos = jnp.asarray(pos, jnp.int32)
    if table.ndim != 2 or table.shape[0] != q_i.shape[0] \
            or pos.shape != (q_i.shape[0],):
        raise ValueError(
            f"table{table.shape} must be (slots, n_blk) and pos{pos.shape} "
            f"(slots,) for {q_i.shape[0]} slots")
    if impl == "pallas" and not _index_eligible(q_i, pool_k):
        raise ValueError(
            f"impl='pallas' requires kernel-eligible operands (head_dim "
            f"and block_size multiples of 128, q_i in the pool's dtype); "
            f"got q_i{q_i.shape} {q_i.dtype} pool{pool_k.shape} "
            f"{pool_k.dtype}")
    if impl == "pallas" or (impl == "auto"
                            and uses_index_kernel(q_i, pool_k)):
        if active is not None:
            pos = jnp.where(jnp.asarray(active).astype(bool), pos, -1)
        return _pallas_index(q_i, w, pool_k, table, pos,
                             interpret=not _on_tpu())
    return index_rows_scores(q_i, block_gather(pool_k, table)[:, :, 0], w)


def sparse_rows_gather(pool_c, table, rows):
    """The rows of a latent pool that a selection names, a slot at a
    time: ``rows`` ``(slots, k)`` int32 positions (negative: none), each
    found at ``pool_c[table[s, t // block_size], t % block_size]``;
    ``(slots, k, width)``, whatever row a negative position or an
    unmapped page falls on (the caller masks by ``rows >= 0``).  Nothing
    but those ``slots * k`` rows is read."""
    nb, bs, _, width = pool_c.shape
    at = jnp.maximum(rows, 0)
    # The page of each position, by comparison against the table's
    # columns: a look-up of slots x k scalars as a gather costs a TPU
    # more than the rows' own gather is worth (0.13 ms for 32,768).
    column = jnp.arange(table.shape[1], dtype=jnp.int32)
    page = jnp.sum(jnp.where((at // bs)[:, :, None] == column,
                             table[:, None, :], 0), axis=-1)
    flat = jnp.clip(page, 0, nb - 1) * bs + at % bs
    with jax.named_scope(SPARSE_GATHER_SCOPE):
        return pool_c.reshape(nb * bs, width).at[flat].get(
            mode="promise_in_bounds")


def _sparse_chunk(q, pool_c, k: int, v_width: int) -> int:
    """Rows of the gathered selection one grid step of the latent kernel
    takes (its "page"): the largest that divides ``k`` and that the
    kernel is eligible for, 0 where there is none."""
    like = lambda c: jax.ShapeDtypeStruct((1, c, 1, pool_c.shape[3]),
                                          pool_c.dtype)
    return next((c for c in (512, 256, 128) if k % c == 0
                 and _eligible(q, like(c), v_width)), 0)


def paged_sparse_latent_attention(q, pool_c, table, rows, *, v_width: int,
                                  scale: float, impl: str = "auto"):
    """Attention of one query row per slot over the rows of a LATENT pool
    that a selection names, and no others.

    ``q``, ``pool_c``, ``table``, ``v_width``, ``scale`` as
    :func:`paged_latent_attention` has them; ``rows`` ``(slots, k)``
    int32: the positions slot ``s`` attends, those that mean one (``>=
    0``) FIRST and ``-1`` behind them, as
    :func:`~mpi4torch_tpu.models.transformer.select_rows` hands them
    over (a slot with none returns zeros).  Head ``h`` weighs the named
    rows by ``softmax(scale * q[s, h] . row)`` and returns the weighted
    sum of their first ``v_width`` channels, ``(slots, heads,
    v_width)``.

    The rows are gathered by page and offset through the table
    (:func:`sparse_rows_gather`: ``slots * k`` rows of the pool are read,
    whatever the context's length) and attended by the latent kernel
    over the gathered rows, a chunk of them a grid step (on a TPU, for
    the shapes :func:`paged_latent_attention`'s kernel takes and ``k`` a
    multiple of 128), else by :func:`latent_rows_attention`, the
    oracle."""
    if impl not in ("auto", "pallas", "jnp"):
        raise ValueError(f"unknown impl {impl!r}")
    if q.ndim != 3 or pool_c.ndim != 4 or pool_c.shape[2] != 1 \
            or q.shape[2] != pool_c.shape[3]:
        raise ValueError(
            f"q{q.shape} must be (slots, heads, width) and the latent "
            f"pool{pool_c.shape} (num_blocks, block_size, 1, width) of "
            "the same width")
    table = jnp.asarray(table, jnp.int32)
    rows = jnp.asarray(rows, jnp.int32)
    if table.ndim != 2 or table.shape[0] != q.shape[0] or rows.ndim != 2 \
            or rows.shape[0] != q.shape[0]:
        raise ValueError(
            f"table{table.shape} must be (slots, n_blk) and rows"
            f"{rows.shape} (slots, k) for {q.shape[0]} slots")
    slots, k = rows.shape
    got = sparse_rows_gather(pool_c, table, rows)
    n_named = jnp.sum(rows >= 0, axis=-1, dtype=jnp.int32)
    chunk = _sparse_chunk(q, pool_c, k, v_width)
    if impl == "pallas" and not chunk:
        raise ValueError(
            f"impl='pallas' requires kernel-eligible operands (width and "
            f"v_width multiples of 128, k a multiple of 128, q in the "
            f"pool's dtype); got q{q.shape} {q.dtype} pool{pool_c.shape} "
            f"{pool_c.dtype} v_width={v_width} k={k}")
    if impl == "pallas" or (impl == "auto" and chunk and _on_tpu()):
        # The gathered rows as a pool of their own, a slot's chunks one
        # after another: the latent kernel reads chunks up to the last
        # named row.
        n_chunks = k // chunk
        return _pallas_latent(
            q, got.reshape(slots * n_chunks, chunk, 1, -1),
            jnp.arange(slots * n_chunks, dtype=jnp.int32).reshape(
                slots, n_chunks),
            n_named - 1, v_width, float(scale), interpret=not _on_tpu(),
            name=KERNEL_NAMES[3])
    return latent_rows_attention(q, got, n_named - 1, v_width=v_width,
                                 scale=scale)
