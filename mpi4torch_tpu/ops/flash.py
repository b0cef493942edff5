"""Fused block attention (flash-style) — the TPU hot-op kernel.

The ring/dense attention in ``parallel.attention`` is algebraically a
sequence of *block attention* calls merged by an online softmax.  This
module provides that block primitive two ways behind one signature:

* a Pallas TPU kernel (`pltpu`): q tiles stream through VMEM, the KV loop
  runs fused in-core (scores, masking, online softmax, PV accumulation all
  without materializing the (q, k) score matrix in HBM), MXU matmuls in
  f32 accumulation.  The tiles are not constants: :func:`tile_plan`
  chooses each kernel's q and KV tile from the call's shape (sequence
  lengths, head size, dtype), wide enough to keep the MXU fed, and counts
  the VMEM the kernel stages with them; the loops skip the tiles no query
  attends and mask only those the causal diagonal or the window's edge
  crosses (:func:`masked_tile_share` counts both without a trace);
* a pure-jnp fallback with identical semantics for ineligible shapes and
  non-TPU platforms (XLA still fuses it well on CPU; it is the oracle the
  kernel is tested against, tests/test_flash.py).

Returns **normalized** partials ``(out, lse)``: ``out`` is softmax(qkᵀ)v
over the given KV block, ``lse`` the log-sum-exp of the (masked) scores.
Two partials merge exactly (parallel/attention.py ``ring_attention``), so
the primitive composes into context parallelism without renormalization
error.  Fully-masked rows yield ``out = 0`` and ``lse = -BIG`` — the
neutral element of the merge.

Positions are passed as i32 offsets so they may be *traced* values —
under SPMD the block owner is rank-symbolic (``lax.axis_index``
arithmetic, SURVEY.md §7 hard part 4).  Integer positions are exact up
to 2^31-1 total tokens (an earlier f32 encoding silently collided
beyond 2^24 — the long-context regime this module exists for).

Differentiable via ``jax.custom_vjp``: the backward recomputes the block
scores (flash-style rematerialization; residuals are q/k/v/out/lse only)
and is shared by both forward paths.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

NEG_BIG = -1e30
# The floor of every tile: one MXU pass and one lane tile wide.  The
# tiles a call actually gets come from :func:`tile_plan`.
_MIN_TILE = 128
# Per-row statistics (lse, and the dq kernel's delta - dlse) cross the
# kernel boundary broadcast along a full lane tile: a (qt,) vector in
# sublane orientation cannot be stored to / loaded from a lane-oriented
# row without a relayout Mosaic may reject, so the stats ride as
# (rows, 128) with the value replicated across lanes — the layout jax's
# own TPU flash kernel uses (MIN_BLOCK_SIZE in
# jax/experimental/pallas/ops/tpu/flash_attention.py).  The dk/dv kernel
# works on transposed score tiles and takes the same statistics as lane-
# oriented rows, replicated over one sublane tile instead.
_STAT_LANES = 128
_SUBLANES = 8

# The kernels stage one head's whole KV block (forward, dq) or whole
# q + dO block (dk/dv) in VMEM per grid step: the loop over it runs
# in-core.  One copy of such a pair may take this much; longer local
# blocks are chunked (``flash_attention``) or fall back to the jnp path
# (ring attention keeps per-rank blocks short anyway).
_KV_VMEM_BUDGET = 8 * 1024 * 1024
# What Mosaic grants a kernel that asks for nothing, and the most a plan
# may ask for (the smallest VMEM of a supported chip is 64 MiB; the v5e
# has 128).  A plan over the default sets ``vmem_limit_bytes`` to its
# own estimate.
_DEFAULT_SCOPED_VMEM = 16 * 1024 * 1024
_VMEM_CAP = 48 * 1024 * 1024
# Room for what Mosaic allocates for itself beside a kernel's blocks and
# values: its internal scratch, and a share that grows with the staged
# blocks (the plain count below was up to 1.4 MiB of 21 short), so a
# sixteenth on top and this much.
_MOSAIC_SCRATCH = 1024 * 1024

# Stable names of the three Mosaic kernels (forward, backward dq,
# backward dk/dv): what a lowered program's ``kernel_name`` attributes
# and a profiler trace's kernel events are matched against.
KERNEL_NAMES = ("mpi4torch_flash_fwd", "mpi4torch_flash_bwd_dq",
                "mpi4torch_flash_bwd_dkv")
# ``checkpoint_name``s of the forward's ``(out, lse)`` where they become
# the backward's residuals: a ``jax.checkpoint`` policy that lists them
# keeps the pair and does not run the forward again; under any other
# policy, and outside a checkpoint region, the names do nothing.
RESIDUAL_NAMES = ("flash_out", "flash_lse")


def _lane_pad(d: int) -> int:
    """Head dim as staged in VMEM: the next lane multiple (128)."""
    return 128 * ((d + 127) // 128)


# ---------------------------------------------------------------------------
# The tile plan: which tiles a call gets, and what they cost in VMEM
# ---------------------------------------------------------------------------


class KernelTiles(NamedTuple):
    """One kernel's tiles and the VMEM bytes it stages with them (blocks
    double-buffered, the in-core loop's temporaries included)."""
    q_tile: int
    kv_tile: int
    vmem_bytes: int


class TilePlan(NamedTuple):
    """What :func:`tile_plan` decides for one call shape.  ``fwd`` is
    ``None`` where the forward kernel cannot be launched, ``dq`` / ``dkv``
    where the backward kernels cannot."""
    fwd: Optional[KernelTiles]
    dq: Optional[KernelTiles]
    dkv: Optional[KernelTiles]


def _vmem_bytes(kernel: str, qt: int, kt: int, sq: int, sk: int, dp: int,
                isz: int) -> int:
    """VMEM one grid step of ``kernel`` stages at tiles ``(qt, kt)``:
    every BlockSpec operand twice (Pallas double-buffers them) plus the
    loop body's float32 temporaries.  The temporaries are counted as
    whole ``(qt, kt)`` slabs (scores, ``p``, its copy in the operand
    dtype, the select; backward also ``dP`` and ``ds``), which is what
    Mosaic materializes for tiles wider than the register file; a
    float32 operand of a product is split into three bfloat16 pieces
    under ``HIGHEST``, one and a half slabs more for each score-sized
    operand (``p``; ``ds``; both in dk/dv).  Held against Mosaic's own
    count by compiling every planned launch for a described v5e
    (bfloat16 and float32, head sizes 64 to 256, 512 to 16,384 tokens:
    the count is never under it, and over it by a few MiB)."""
    f32 = 4
    tile = qt * kt * f32
    split = 3 * tile // 2 if isz == 4 else 0
    if kernel == "fwd":
        blocks = (2 * qt * dp * isz + qt * _STAT_LANES * f32   # q, out, lse
                  + 2 * sk * dp * isz)                         # K, V whole
        temps = 4 * tile + split + 3 * qt * dp * f32
    elif kernel == "dq":
        blocks = (3 * qt * dp * isz + 2 * qt * _STAT_LANES * f32
                  + 2 * sk * dp * isz)
        temps = 6 * tile + split + 2 * qt * dp * f32
    else:
        # dk/dv leave as float32 partials under GQA: counted so always.
        blocks = (2 * kt * dp * isz + 2 * kt * dp * f32
                  + 2 * sq * dp * isz                          # q, dO whole
                  + 2 * _SUBLANES * sq * f32)                  # lse, dd rows
        temps = 6 * tile + 2 * split + 4 * kt * dp * f32
    return (2 * blocks + temps) * 17 // 16 + _MOSAIC_SCRATCH


def kernel_tiles(kernel: str, qt: int, kt: int, sq: int, sk: int,
                 head_dim: int, dtype) -> KernelTiles:
    """``kernel``'s tiles ``(qt, kt)`` with the VMEM count that goes with
    them for a call of this shape: what :func:`tile_plan` hands out, and
    how a test or the chip probe spells an explicit plan."""
    return KernelTiles(qt, kt, _vmem_bytes(
        kernel, qt, kt, sq, sk, _lane_pad(head_dim),
        jnp.dtype(dtype).itemsize))


def _tile_candidates(s: int):
    """Tile lengths a sequence of ``s`` can be cut into, widest first:
    powers of two from 1,024 down to the floor that divide it, or the
    whole of a sequence shorter than the floor."""
    if s <= _MIN_TILE:
        return (s,)
    return tuple(t for t in (1024, 512, 256, _MIN_TILE) if s % t == 0)


# The widest tiles each kernel is given, (q tile, KV tile): where every
# shape the benchmark's cells send ran fastest, or within 3% of it, in
# the chip sweep of all sixteen pairs of 128..1,024 (PERF.md section 6,
# PR 33; v5e, bf16, head sizes 128 and 192 staged 256, 256 to 16,384
# tokens).  Wider KV tiles amortize the lane reductions of the online
# softmax and the carry's rescale; wider q tiles stream more rows
# through each staged MXU weight tile; past 512 the diagonal's waste
# (visited over attended pairs is (n + 1) / n for n tiles a side) and
# the float32 score slab's trips through VMEM cost more than they save.
# The forward, with its (q tile, head) accumulator rescaled every KV
# tile, is best at a narrower q tile than the backward kernels.
_WIDEST_TILES = {"fwd": (256, 512), "dq": (512, 512), "dkv": (512, 512)}
# float32 operands contract in several MXU passes under ``HIGHEST``: the
# products dominate, wide tiles gain less (1.7x over the floor in the
# forward, 1.1x in the backward) and 256 x 256 was the fastest pair of
# the sixteen in all three kernels (same sweep, float32, head sizes 128
# and 192).
_WIDEST_TILES_F32 = (256, 256)


def tile_plan(sq: int, sk: int, head_dim: int, dtype, causal: bool = False,
              window: int = 0) -> TilePlan:
    """The q tile, the KV tile and the staged VMEM bytes of each of the
    three kernels for a call of ``sq`` queries against ``sk`` keys, from
    nothing but the call's shape: pure integer arithmetic, once a trace.
    Each kernel gets the widest tiles up to ``_WIDEST_TILES`` (float32:
    ``_WIDEST_TILES_F32``) that divide the sequence (a 256-token prefill
    gets 256 x 256, a 192-wide head staged 256 wide the same tiles with
    twice the bytes); a kernel whose
    whole-sequence operands pass ``_KV_VMEM_BUDGET``, or whose staging
    would pass ``_VMEM_CAP``, gets none.  ``_eligible``, ``_bwd_eligible``,
    ``_kv_chunk_for`` and both launches ask it, so the predicate and the
    launch cannot disagree."""
    del causal, window      # they narrow a loop's span, not its tiles
    dp, isz = _lane_pad(head_dim), jnp.dtype(dtype).itemsize
    q_c, k_c = _tile_candidates(sq), _tile_candidates(sk)
    if head_dim < 64 or not q_c or not k_c:
        return TilePlan(None, None, None)

    def pick(kernel: str):
        if 2 * (sq if kernel == "dkv" else sk) * dp * isz > _KV_VMEM_BUDGET:
            return None
        widest = _WIDEST_TILES_F32 if isz == 4 else _WIDEST_TILES[kernel]
        qt, kt = (next((t for t in cands if t <= most), cands[-1])
                  for cands, most in zip((q_c, k_c), widest))
        tiles = kernel_tiles(kernel, qt, kt, sq, sk, head_dim, dtype)
        return tiles if tiles.vmem_bytes <= _VMEM_CAP else None

    fwd = pick("fwd")
    if fwd is None:
        return TilePlan(None, None, None)
    dq, dkv = pick("dq"), pick("dkv")
    if dq is None or dkv is None:
        dq = dkv = None
    return TilePlan(fwd, dq, dkv)


def _eligible(q, k) -> bool:
    """Shapes the TPU kernel handles: those :func:`tile_plan` has forward
    tiles for — sequence lengths a tile divides and one head's staged KV
    within the budget.  head_dim need not be a lane multiple — the
    kernel zero-pads it to the next multiple of 128 (d=64/96 pay ≤2x
    staged bytes, still far cheaper than the jnp path's HBM score
    matrix).  d < 64 would waste >2x MXU/VMEM on padding, so those
    shapes take the jnp fallback (XLA fuses them fine)."""
    return tile_plan(q.shape[1], k.shape[1], q.shape[3], k.dtype).fwd \
        is not None


def _on_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"


# ---------------------------------------------------------------------------
# jnp reference path (and CPU fallback)
# ---------------------------------------------------------------------------


def _compute_dtype(q):
    # At least f32; f64 inputs keep f64 (the x64 test suite's oracles
    # compare at 1e-12 — the fallback must not down-cast).
    return jnp.promote_types(q.dtype, jnp.float32)


def dot_precision(dtype):
    """Contract precision for the attention matmuls, chosen by operand dtype.

    Under ``precision=DEFAULT`` the TPU MXU contracts even f32 operands in
    single bf16 passes — measured ~3e0 max relative error against the f32
    product on a v5e.  That is the right trade for bf16 inputs (one fast
    pass; Mosaic rejects an fp32 contract precision on bf16 vectors
    outright), but it silently strips an f32 attention call to ~3
    significant digits and makes kernel-vs-oracle comparison ill-posed:
    each side reassociates *different* bf16 partials.  So f32-or-wider
    operands pin ``HIGHEST`` (the MXU's multi-pass f32-exact algorithm)
    and narrower ones keep the single-pass default.  CPU ignores the flag
    either way, so the x64 oracle suite is unaffected."""
    return (jax.lax.Precision.HIGHEST
            if jnp.dtype(dtype).itemsize >= 4 else None)


def _gqa_groups(q, k) -> int:
    """Query heads per KV head (grouped-query attention).  1 = plain MHA;
    q head ``h`` attends through KV head ``h // g`` (the repeat-interleave
    convention).  Head counts are validated once at the public entry
    (:func:`flash_block_attention`)."""
    return q.shape[2] // k.shape[2]


def _group_repeat_kv(k, g: int):
    """(b, sk, h_kv, d) -> (b, sk, h_kv*g, d) with each KV head repeated
    ``g`` times consecutively — the jnp/oracle realization of the
    ``h // g`` mapping.  The kernels never do this: their KV BlockSpec
    index maps point q-head grid rows straight at the shared KV head, so
    GQA's HBM saving is real on the kernel path."""
    return k if g == 1 else jnp.repeat(k, g, axis=2)


def _group_sum(dkv, b: int, h_kv: int, g: int):
    """Sum per-q-head dk/dv partials back onto the shared KV heads:
    (b, sk, h_kv*g, d) -> (b, sk, h_kv, d)."""
    if g == 1:
        return dkv
    sk, d = dkv.shape[1], dkv.shape[3]
    return dkv.reshape(b, sk, h_kv, g, d).sum(axis=3)


def _kv_row(i, h: int, h_kv: int, g: int):
    """BlockSpec index-map arithmetic shared by all three kernels: grid
    rows walk q heads (``b*h`` rows, head-minor); the KV operand row for
    q-head grid row ``i`` is its batch's shared KV head ``(i % h) // g``
    — GQA resolved in the index map, so KV is never duplicated in HBM."""
    return (i // h) * h_kv + (i % h) // g


def _jnp_block(q, k, v, q_off, kv_off, causal: bool, window: int = 0):
    ct = _compute_dtype(q)
    b, sq, h, d = q.shape
    sk = k.shape[1]
    g = _gqa_groups(q, k)
    k, v = _group_repeat_kv(k, g), _group_repeat_kv(v, g)
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, ct))
    # Precision keyed on the INPUT dtype: bf16 inputs keep the single-pass
    # contract even though operands are staged in f32 here, matching the
    # kernel path's cost and accuracy (see dot_precision).
    prec = dot_precision(q.dtype)
    s = jnp.einsum("bqhd,bkhd->bqhk", q.astype(ct), k.astype(ct),
                   precision=prec) * scale
    bmask = None
    if causal:
        batched = q_off.ndim > 0 or kv_off.ndim > 0
        if not batched:
            q_pos = q_off + jnp.arange(sq, dtype=jnp.int32)
            kv_pos = kv_off + jnp.arange(sk, dtype=jnp.int32)
            mask = q_pos[:, None] >= kv_pos[None, :]
            if window:
                # Sliding window: q attends the last `window` positions
                # (itself included) — q_pos - window < kv_pos <= q_pos.
                mask &= (q_pos[:, None] - kv_pos[None, :]) < window
            bmask = mask[None, :, None, :]
        else:
            # Per-row offsets (the continuous-batching decode path,
            # mpi4torch_tpu.serve): each batch row sits at its OWN
            # global position, so the causal/window frontier is per
            # row.  Same mask algebra, one extra leading axis.
            q_pos = q_off[..., None] + jnp.arange(sq, dtype=jnp.int32)
            kv_pos = kv_off[..., None] + jnp.arange(sk, dtype=jnp.int32)
            mask = (q_pos[..., :, None] >= kv_pos[..., None, :])
            if window:
                mask &= (q_pos[..., :, None]
                         - kv_pos[..., None, :]) < window
            mask = jnp.broadcast_to(mask, (b, sq, sk))
            bmask = mask[:, :, None, :]
    out, lse = _jnp_softmax(s, v.astype(ct), bmask, prec)
    return out.astype(q.dtype), lse


def _jnp_softmax(s, v, bmask, prec):
    """Scores ``s`` ``(b, q, h, k)`` under ``bmask`` (broadcastable to
    them; ``None``: all of them) into the normalised weighted sum of
    ``v`` ``(b, k, h, d)`` and the scores' log-sum-exp, in ``s``'s
    dtype: a row with nothing to attend gives zeros and ``NEG_BIG``."""
    if bmask is not None:
        s = jnp.where(bmask, s, NEG_BIG)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    if bmask is not None:
        p = jnp.where(bmask, p, 0.0)
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bqhk,bkhd->bqhd", p, v, precision=prec)
    safe_l = jnp.where(l > 0, l, 1.0)
    out = jnp.where(l[..., None] > 0, acc / safe_l[..., None], 0.0)
    lse = jnp.where(l > 0, m + jnp.log(safe_l), NEG_BIG)
    return out, lse


def masked_attention(q, k, v, mask, *, q_offset: int = 0,
                     block_q: int = 512, block_k: int = 2048):
    """Attention of every query over the keys ITS row of ``mask`` names,
    in plain jnp: ``q`` ``(1, sq, h, d)``, ``k`` / ``v`` ``(1, sk, h,
    d)`` of one head count, ``mask`` ``(sq, sk)`` bool, causal already
    (query ``i`` sits at position ``q_offset + i`` and names no key
    beyond it).  The arithmetic of the jnp block path (scores, softmax
    and sums in float32 at the least); a query block at a time over the
    key blocks up to its diagonal, the partials merged by the
    online-softmax rule in float32, so that no array of ``sq x sk``
    scores is formed.  A row that names nothing gives zeros."""
    ct = _compute_dtype(q)
    _, sq, h, d = q.shape
    sk = k.shape[1]
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, ct))
    prec = dot_precision(q.dtype)

    def part(qb, kb, vb, mb):
        s = jnp.einsum("bqhd,bkhd->bqhk", qb.astype(ct), kb.astype(ct),
                       precision=prec) * scale
        return _jnp_softmax(s, vb.astype(ct), mb[None, :, None, :], prec)

    if sq <= block_q and sk <= block_k:
        return part(q, k, v, mask)[0].astype(q.dtype)
    nq, nk = -(-sq // block_q), -(-sk // block_k)
    pad = lambda x, n, axis: jnp.pad(
        x, [(0, n - x.shape[a]) if a == axis else (0, 0)
            for a in range(x.ndim)])
    q = pad(q, nq * block_q, 1)
    k, v = pad(k, nk * block_k, 1), pad(v, nk * block_k, 1)
    mask = pad(pad(mask, nq * block_q, 0), nk * block_k, 1)
    cut = jax.lax.dynamic_slice_in_dim

    def q_block(i):
        qb = cut(q, i * block_q, block_q, 1)
        mrows = cut(mask, i * block_q, block_q, 0)
        # Key blocks beyond the block's last query hold nothing it names.
        n_live = jnp.minimum(
            (q_offset + (i + 1) * block_q - 1) // block_k + 1, nk)

        def k_block(j, carry):
            out, lse = carry
            o_b, lse_b = part(qb, cut(k, j * block_k, block_k, 1),
                              cut(v, j * block_k, block_k, 1),
                              cut(mrows, j * block_k, block_k, 1))
            new = jnp.logaddexp(lse, lse_b)
            return (out * jnp.exp(lse - new)[..., None]
                    + o_b * jnp.exp(lse_b - new)[..., None], new)

        out, _ = jax.lax.fori_loop(
            0, n_live, k_block,
            (jnp.zeros((1, block_q, h, v.shape[-1]), ct),
             jnp.full((1, block_q, h), NEG_BIG, ct)))
        return out[0].astype(q.dtype)

    out = jax.lax.map(q_block, jnp.arange(nq, dtype=jnp.int32))
    return out.reshape(1, nq * block_q, h, -1)[:, :sq]


# ---------------------------------------------------------------------------
# Pallas TPU kernel
# ---------------------------------------------------------------------------


def _clip(x, lo, hi):
    """``clip`` on Python ints (the plan's own arithmetic, no trace) or
    on traced scalars (inside a kernel)."""
    if all(isinstance(t, int) for t in (x, lo, hi)):
        return min(max(x, lo), hi)
    return jnp.minimum(jnp.maximum(x, lo), hi)


def _kv_loop_bounds(q_lo, kv_off, qt: int, kt: int, n_kv: int,
                    causal: bool, window: int):
    """KV tiles the forward and dq kernels visit for the q tile whose
    first query sits at ``q_lo``, as ``(a, b, c, d)``: tiles ``[a, d)``
    can hold an attended pair, tiles ``[b, c)`` hold nothing else.

    * ``d``: tiles whose first key <= the tile's LAST query (the causal
      diagonal's outer bound); ``a``: the tile holding the FIRST query's
      earliest visible key ``q_lo - window + 1`` (the window frontier's
      outer bound).  Tiles outside ``[a, d)`` are exactly neutral in the
      online-softmax carry and in the gradients (p is where-masked to
      zero), so skipping them changes no output bit and makes windowed
      attention cost O(window), not O(seq), per q tile.
    * ``c``: tiles whose LAST key <= the FIRST query, ``b``: tiles whose
      first key >= the LAST query's window start — the same arithmetic
      on the tile's other corner.  On ``[b, c)`` every pair is attended:
      the mask is all true, a ``where`` on it the identity, so those
      tiles run without iota, compare or select.

    Offsets may be traced scalars (rank-symbolic under SPMD): the bounds
    feed dynamic fori_loops.  On Python ints this is plain arithmetic,
    which is how :func:`masked_tile_share` counts without a trace."""
    if not causal:
        return 0, 0, n_kv, n_kv
    q_hi = q_lo + qt - 1
    d = _clip((q_hi - kv_off) // kt + 1, 0, n_kv)
    c = (q_lo - kv_off + 1) // kt
    if window:
        a = _clip((q_lo - window + 1 - kv_off) // kt, 0, n_kv)
        b = _clip(-((kv_off - (q_hi - window + 1)) // kt), a, d)
    else:
        a = b = 0
    return a, b, _clip(c, b, d), d


def _q_loop_bounds(kv_lo, q_off, qt: int, kt: int, n_q: int,
                   causal: bool, window: int):
    """The mirror cuts of the dk/dv kernel: q tiles ``[a, d)`` can hold
    a pair attended by the KV tile whose first key sits at ``kv_lo``,
    q tiles ``[b, c)`` hold nothing else.

    * ``a``: the first q tile whose last query reaches ``kv_lo`` (the
      diagonal); ``d``: one past the tile of the farthest query still
      inside any of this KV tile's windows, ``kv_hi + window - 1``.
    * ``b``: the first q tile whose FIRST query >= the tile's LAST key;
      ``c``: q tiles whose LAST query's window still starts at or before
      the tile's FIRST key."""
    if not causal:
        return 0, 0, n_q, n_q
    kv_hi = kv_lo + kt - 1
    a = _clip((kv_lo - q_off) // qt, 0, n_q)
    if window:
        d = _clip((kv_hi + window - 1 - q_off) // qt + 1, 0, n_q)
        c = (kv_lo + window - q_off) // qt
    else:
        c = d = n_q
    b = _clip(-((q_off - kv_hi) // qt), a, d)
    return a, b, _clip(c, b, d), d


def masked_tile_share(sq: int, sk: int, tiles: KernelTiles, causal: bool,
                      window: int = 0, q_offset: int = 0,
                      kv_offset: int = 0, over: str = "kv"):
    """``(visited, masked)``: the tile pairs a kernel's loops visit for
    a call at these (integer) offsets, and how many of them take the
    mask — the kernels' own bounds on Python ints.  ``over="kv"`` is the
    forward and dq kernels' loop (a q tile walks KV tiles), ``"q"`` the
    dk/dv kernel's."""
    qt, kt = tiles.q_tile, tiles.kv_tile
    n_q, n_kv = sq // qt, sk // kt
    visited = masked = 0
    for t in range(n_q if over == "kv" else n_kv):
        if over == "kv":
            a, b, c, d = _kv_loop_bounds(q_offset + t * qt, kv_offset, qt,
                                         kt, n_kv, causal, window)
        else:
            a, b, c, d = _q_loop_bounds(kv_offset + t * kt, q_offset, qt,
                                        kt, n_q, causal, window)
        visited += d - a
        masked += (b - a) + (d - c)
    return visited, masked


def _split_loop(bounds, body, carry):
    """Run ``body(j, carry, masked)`` over the tiles ``[a, d)`` of
    ``bounds``: the interior ``[b, c)`` unmasked in one loop, the two
    edges ``[a, b)`` and ``[c, d)`` masked in another (one traced copy of
    each body).  Every tile is visited once; the order differs from a
    single sweep only in the order of float32 sums."""
    a, b, c, d = bounds
    carry = jax.lax.fori_loop(b, c, lambda j, x: body(j, x, False), carry)
    low, n_edge = b - a, (b - a) + (d - c)
    if isinstance(n_edge, int) and n_edge == 0:
        return carry                    # no mask at all (not causal)

    def edge(t, x):
        return body(jnp.where(t < low, a + t, c + (t - low)), x, True)

    return jax.lax.fori_loop(0, n_edge, edge, carry)


def _compiler_params(vmem_bytes: int):
    """CompilerParams shared by all three kernels: both grid dims are
    fully independent (each step writes a distinct output block; all
    reduction lives in in-core fori_loops), so Mosaic may pipeline the
    grid and split it across cores on megacore parts.  A plan that needs
    more than Mosaic's default scoped VMEM asks for its own estimate."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel"),
        vmem_limit_bytes=(vmem_bytes if vmem_bytes > _DEFAULT_SCOPED_VMEM
                          else None))


_NT = (((1,), (1,)), ((), ()))      # a @ b.T: contract the minor dims
_NN = (((1,), (0,)), ((), ()))      # a @ b


def _tile_mask(q_lo, kv_lo, qt: int, kt: int, window: int,
               transposed: bool = False):
    """Causal (and window) mask of the tile whose first query and key
    sit at global positions ``q_lo`` / ``kv_lo``: ``(qt, kt)``, or
    ``(kt, qt)`` for the dk/dv kernel's transposed tiles."""
    i32 = jnp.int32
    q_shape, kv_shape = ((1, qt), (kt, 1)) if transposed \
        else ((qt, 1), (1, kt))
    q_pos = q_lo + jax.lax.broadcasted_iota(i32, q_shape, int(transposed))
    kv_pos = kv_lo + jax.lax.broadcasted_iota(i32, kv_shape,
                                              int(not transposed))
    mask = q_pos >= kv_pos
    if window:
        mask &= (q_pos - kv_pos) < window
    return mask


def _fwd_kernel(qoff_ref, kvoff_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                *, causal: bool, kv_tile: int, window: int = 0):
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    qt, d = q_ref.shape[1], q_ref.shape[2]
    n_kv = k_ref.shape[1] // kv_tile

    # Operands stay in their input dtype for the MXU dots (bf16 inputs
    # run at the MXU's bf16 rate; an up-front astype(f32) would force
    # f32-rate multiplies) — accumulation is f32 via
    # preferred_element_type.  q arrives already multiplied by the
    # softmax scale (once a call, in the layout change outside), so no
    # score tile pays for it.  f32 operands pin the f32-exact contract
    # (see dot_precision).
    prec = dot_precision(q_ref.dtype)
    qb = q_ref[0]                                           # (QT, D)
    q_lo = qoff_ref[0, 0] + pl.program_id(1) * qt
    kv_off = kvoff_ref[0, 0]

    def body(j, carry, masked: bool):
        m, l, acc = carry
        rows = pl.ds(pl.multiple_of(j * kv_tile, kv_tile), kv_tile)
        kb = k_ref[0, rows, :]
        vb = v_ref[0, rows, :]
        s = jax.lax.dot_general(qb, kb, _NT, preferred_element_type=f32,
                                precision=prec)             # (QT, KT)
        if masked:
            mask = _tile_mask(q_lo, kv_off + j * kv_tile, qt, kv_tile,
                              window)
            s = jnp.where(mask, s, NEG_BIG)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        if masked:
            p = jnp.where(mask, p, 0.0)
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * corr + jax.lax.dot_general(
            p.astype(vb.dtype), vb, _NN, preferred_element_type=f32,
            precision=prec)
        return m_new, l, acc

    m, l, acc = _split_loop(
        _kv_loop_bounds(q_lo, kv_off, qt, kv_tile, n_kv, causal, window),
        body, (jnp.full((qt, 1), NEG_BIG, f32), jnp.zeros((qt, 1), f32),
               jnp.zeros((qt, d), f32)))

    nonzero = l > 0
    safe_l = jnp.where(nonzero, l, 1.0)
    o_ref[0] = jnp.where(nonzero, acc / safe_l, 0.0).astype(o_ref.dtype)
    lse = jnp.where(nonzero, m + jnp.log(safe_l), NEG_BIG)
    # lse is a (qt, 1) column (row stats live along sublanes); writing it
    # to a lane-oriented row would be a sublane->lane relayout Mosaic may
    # not support.  Instead broadcast along lanes into a (qt, 128) tile —
    # the same scheme jax's own TPU flash kernel uses for its l/m outputs
    # (pallas/ops/tpu/flash_attention.py MIN_BLOCK_SIZE) — and let the
    # caller slice lane 0 outside the kernel.
    lse_ref[0] = jax.lax.broadcast_in_dim(lse, (lse.shape[0], _STAT_LANES),
                                          (0, 1))


def _to_bh(x, dp: int, scale=None):
    """(b, s, nh, d) -> (b*nh, s, dp): heads to the front, head_dim
    zero-padded to the lane width (zeros leave every dot product
    unchanged, so only an output slice is needed to undo it), and for q
    the softmax scale folded in (float32 product, rounded once to the
    operand dtype) — all one layout-changing copy."""
    b, s, nh, d = x.shape
    if scale is not None:
        x = (x.astype(jnp.float32) * scale).astype(x.dtype)
    x = x.transpose(0, 2, 1, 3).reshape(b * nh, s, d)
    if dp != d:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, dp - d)))
    return x


def _from_bh(x, b: int, d: int):
    """(b*nh, s, dp) -> (b, s, nh, d)."""
    bh, s, _ = x.shape
    return x[:, :, :d].reshape(b, bh // b, s, d).transpose(0, 2, 1, 3)


def _offsets(q_off, kv_off):
    return (jnp.asarray(q_off, jnp.int32).reshape(1, 1),
            jnp.asarray(kv_off, jnp.int32).reshape(1, 1))


def _softmax_scale(d: int) -> float:
    """1/sqrt(head_dim) of the model's TRUE head_dim (padded columns are
    zero and change no dot product)."""
    return float(d) ** -0.5


def _pallas_block(q, k, v, q_off, kv_off, causal: bool, interpret: bool,
                  window: int = 0, tiles: Optional[KernelTiles] = None):
    """The forward launch.  ``tiles`` overrides the plan's (tests and the
    chip probe sweep explicit plans; no caller of the library does)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, sq, h, d = q.shape
    sk, h_kv = k.shape[1], k.shape[2]
    bh = b * h
    dp = _lane_pad(d)
    qt, kt, vmem_bytes = tiles or tile_plan(sq, sk, d, q.dtype, causal,
                                            window).fwd
    kv_row = functools.partial(_kv_row, h=h, h_kv=h_kv, g=_gqa_groups(q, k))

    qoff, kvoff = _offsets(q_off, kv_off)
    smem = functools.partial(pl.BlockSpec, memory_space=pltpu.SMEM)
    vmem = functools.partial(pl.BlockSpec, memory_space=pltpu.VMEM)
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, causal=causal, kv_tile=kt,
                          window=window),
        out_shape=(
            jax.ShapeDtypeStruct((bh, sq, dp), q.dtype),
            # lse rides lane-broadcast as (bh, sq, _STAT_LANES): Mosaic
            # requires a block's last two dims to each be sublane/lane-
            # divisible (8, 128) or equal to the array dim.  Round 3's
            # 2-D (bh, sq) array with block (1, qt) violated the sublane
            # rule (1 ∤ 8, 1 ≠ bh) and failed compiled lowering at every
            # eligible shape; block (1, qt, 128) is legal (qt is either
            # 128-divisible or the full sq), and the lane broadcast also
            # avoids an in-kernel sublane->lane relayout of the (qt,)
            # stats vector (see _STAT_LANES).
            jax.ShapeDtypeStruct((bh, sq, _STAT_LANES), jnp.float32),
        ),
        grid=(bh, sq // qt),
        in_specs=[
            smem((1, 1), lambda i, j: (0, 0)),
            smem((1, 1), lambda i, j: (0, 0)),
            vmem((1, qt, dp), lambda i, j: (i, j, 0)),
            vmem((1, sk, dp), lambda i, j: (kv_row(i), 0, 0)),
            vmem((1, sk, dp), lambda i, j: (kv_row(i), 0, 0)),
        ],
        out_specs=(
            vmem((1, qt, dp), lambda i, j: (i, j, 0)),
            vmem((1, qt, _STAT_LANES), lambda i, j: (i, j, 0)),
        ),
        compiler_params=_compiler_params(vmem_bytes),
        interpret=interpret,
        name=KERNEL_NAMES[0],
    )(qoff, kvoff, _to_bh(q, dp, _softmax_scale(d)), _to_bh(k, dp),
      _to_bh(v, dp))

    lse = lse[:, :, 0].reshape(b, h, sq).transpose(0, 2, 1)
    return _from_bh(out, b, d), lse


# ---------------------------------------------------------------------------
# Pallas TPU backward kernels (flash backward: dq; dk/dv)
# ---------------------------------------------------------------------------
#
# Training is ~2/3 backward FLOPs; a fused forward alone leaves the score
# matrix materializing in HBM on the way back (round-3 verdict #4).  The
# standard flash-backward split: one kernel tiles over q (KV loop
# in-core, accumulates dq), one tiles over kv (q loop in-core,
# accumulates dk/dv).  Both recompute p = exp(s - lse) from the forward
# residuals — scores never hit HBM in either direction.  The jnp
# backward below stays as the oracle (tests/test_flash.py).
#
# ``lse`` and ``dd = delta - dlse`` are the only row statistics: fusing
# delta and dlse into one array saves a third of the staged stat VMEM
# (they only ever appear as this difference: ds = p*(dp - delta + dlse)).
# The dlse term is live under ring attention, whose merge consumes lse.
# Fully-masked rows have lse = NEG_BIG, making the raw exp() garbage;
# the mask ``where`` zeroes those entries (same order of operations as
# the jnp oracle) — and such rows lie on edge tiles only: an interior
# tile attends every pair, so each of its rows has a finite lse.


def _bwd_dq_kernel(qoff_ref, kvoff_ref, q_ref, k_ref, v_ref, do_ref,
                   lse_ref, dd_ref, dq_ref,
                   *, causal: bool, kv_tile: int, scale: float,
                   window: int = 0):
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    qt, d = q_ref.shape[1], q_ref.shape[2]
    n_kv = k_ref.shape[1] // kv_tile
    prec = dot_precision(q_ref.dtype)

    qb = q_ref[0]                    # already times the softmax scale
    dob = do_ref[0]
    # One lane of the broadcast statistics, as a (QT, 1) column: the
    # subtractions below broadcast it along lanes, whatever the KV tile.
    lse_c = lse_ref[0][:, :1]
    dd_c = dd_ref[0][:, :1]
    q_lo = qoff_ref[0, 0] + pl.program_id(1) * qt
    kv_off = kvoff_ref[0, 0]

    def body(j, dq, masked: bool):
        rows = pl.ds(pl.multiple_of(j * kv_tile, kv_tile), kv_tile)
        kb = k_ref[0, rows, :]
        vb = v_ref[0, rows, :]
        # Native-dtype MXU operands, f32 accumulation (see _fwd_kernel).
        s = jax.lax.dot_general(qb, kb, _NT, preferred_element_type=f32,
                                precision=prec)             # (QT, KT)
        p = jnp.exp(s - lse_c)
        if masked:
            p = jnp.where(_tile_mask(q_lo, kv_off + j * kv_tile, qt,
                                     kv_tile, window), p, 0.0)
        dp_ = jax.lax.dot_general(dob, vb, _NT, preferred_element_type=f32,
                                  precision=prec)
        ds = p * (dp_ - dd_c)
        return dq + jax.lax.dot_general(
            ds.astype(kb.dtype), kb, _NN, preferred_element_type=f32,
            precision=prec)

    dq = _split_loop(
        _kv_loop_bounds(q_lo, kv_off, qt, kv_tile, n_kv, causal, window),
        body, jnp.zeros((qt, d), f32))
    dq_ref[0] = (dq * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(qoff_ref, kvoff_ref, q_ref, k_ref, v_ref, do_ref,
                    lse_ref, dd_ref, dk_ref, dv_ref,
                    *, causal: bool, q_tile: int, window: int = 0):
    """dk/dv of one KV tile, on TRANSPOSED score tiles ``(KT, QT)``:
    ``k q^T`` and ``v dO^T`` contract minor dims like the forward's
    ``q k^T``, and ``p^T dO`` / ``ds^T q`` are then plain products — no
    tile is transposed on the way to the MXU.  The row statistics arrive
    lane-oriented, ``(q tiles, 8, QT)``, one q tile's row replicated
    over a sublane tile, and broadcast along sublanes."""
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    kt, d = k_ref.shape[1], k_ref.shape[2]
    n_q = q_ref.shape[1] // q_tile
    prec = dot_precision(q_ref.dtype)

    kb = k_ref[0]
    vb = v_ref[0]
    kv_lo = kvoff_ref[0, 0] + pl.program_id(1) * kt
    q_off = qoff_ref[0, 0]

    def body(i, carry, masked: bool):
        dk, dv = carry
        rows = pl.ds(pl.multiple_of(i * q_tile, q_tile), q_tile)
        q_t = q_ref[0, rows, :]      # already times the softmax scale
        do_t = do_ref[0, rows, :]
        s_t = jax.lax.dot_general(kb, q_t, _NT, preferred_element_type=f32,
                                  precision=prec)           # (KT, QT)
        p_t = jnp.exp(s_t - lse_ref[0, i][:1])
        if masked:
            p_t = jnp.where(_tile_mask(q_off + i * q_tile, kv_lo, q_tile,
                                       kt, window, transposed=True),
                            p_t, 0.0)
        dv = dv + jax.lax.dot_general(
            p_t.astype(do_t.dtype), do_t, _NN, preferred_element_type=f32,
            precision=prec)                                 # (KT, D)
        dp_t = jax.lax.dot_general(vb, do_t, _NT,
                                   preferred_element_type=f32,
                                   precision=prec)
        ds_t = p_t * (dp_t - dd_ref[0, i][:1])
        # q carries the softmax scale, so this is dk whole.
        dk = dk + jax.lax.dot_general(
            ds_t.astype(q_t.dtype), q_t, _NN, preferred_element_type=f32,
            precision=prec)
        return dk, dv

    zero = jnp.zeros((kt, d), f32)
    dk, dv = _split_loop(
        _q_loop_bounds(kv_lo, q_off, q_tile, kt, n_q, causal, window),
        body, (zero, zero))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _pallas_bwd(q, k, v, do, lse, dd, q_off, kv_off,
                causal: bool, interpret: bool, window: int = 0,
                tiles_dq: Optional[KernelTiles] = None,
                tiles_dkv: Optional[KernelTiles] = None):
    """Fused dq/dk/dv, two launches.  Layout/staging mirrors
    ``_pallas_block``; the row statistics (lse, delta - dlse) ride
    lane-broadcast as (bh, sq, _STAT_LANES) f32 into the dq kernel — the
    same Mosaic-proven scheme as the forward's lse output — and as
    lane-oriented rows into the dk/dv kernel.  ``tiles_*`` override the
    plan's, for tests and the chip probe."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, sq, h, d = q.shape
    sk, h_kv = k.shape[1], k.shape[2]
    g = _gqa_groups(q, k)
    bh = b * h
    dp = _lane_pad(d)
    scale = _softmax_scale(d)
    plan = tile_plan(sq, sk, d, q.dtype, causal, window)
    qt, kt, vmem_dq = tiles_dq or plan.dq
    qt2, kt2, vmem_dkv = tiles_dkv or plan.dkv
    kv_row = functools.partial(_kv_row, h=h, h_kv=h_kv, g=g)

    def stats(x):                       # (b, sq, h) -> (bh, sq) f32
        return x.astype(jnp.float32).transpose(0, 2, 1).reshape(bh, sq)

    def lanes(x):     # -> (bh, sq, _STAT_LANES), lane-broadcast
        return jnp.broadcast_to(x[..., None], (bh, sq, _STAT_LANES))

    def rows(x):      # -> (bh, q tiles, 8, QT), sublane-broadcast
        return jnp.broadcast_to(x.reshape(bh, sq // qt2, 1, qt2),
                                (bh, sq // qt2, _SUBLANES, qt2))

    qb, dob = _to_bh(q, dp, scale), _to_bh(do, dp)
    kb, vb = _to_bh(k, dp), _to_bh(v, dp)
    lse_s, dd_s = stats(lse), stats(dd)
    qoff, kvoff = _offsets(q_off, kv_off)

    smem = functools.partial(pl.BlockSpec, memory_space=pltpu.SMEM)
    vmem = functools.partial(pl.BlockSpec, memory_space=pltpu.VMEM)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, causal=causal, kv_tile=kt,
                          scale=scale, window=window),
        out_shape=jax.ShapeDtypeStruct((bh, sq, dp), q.dtype),
        grid=(bh, sq // qt),
        in_specs=[
            smem((1, 1), lambda i, j: (0, 0)),
            smem((1, 1), lambda i, j: (0, 0)),
            vmem((1, qt, dp), lambda i, j: (i, j, 0)),
            vmem((1, sk, dp), lambda i, j: (kv_row(i), 0, 0)),
            vmem((1, sk, dp), lambda i, j: (kv_row(i), 0, 0)),
            vmem((1, qt, dp), lambda i, j: (i, j, 0)),
            vmem((1, qt, _STAT_LANES), lambda i, j: (i, j, 0)),
            vmem((1, qt, _STAT_LANES), lambda i, j: (i, j, 0)),
        ],
        out_specs=vmem((1, qt, dp), lambda i, j: (i, j, 0)),
        compiler_params=_compiler_params(vmem_dq),
        interpret=interpret,
        name=KERNEL_NAMES[1],
    )(qoff, kvoff, qb, kb, vb, dob, lanes(lse_s), lanes(dd_s))

    # Under GQA (g > 1) the dkv grid still walks q heads: each grid row
    # reads its shared KV head (kv_row) and writes a PER-Q-HEAD partial;
    # the g partials per KV head are summed outside the kernel.  Partials
    # are f32 so the cross-group sum accumulates at the same precision as
    # the in-kernel fori_loop (transient cost: g x f32 dk/dv, freed by
    # the sum — KV itself is still never duplicated).
    n_q2 = sq // qt2
    dk_p, dv_p = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, causal=causal, q_tile=qt2,
                          window=window),
        out_shape=(
            jax.ShapeDtypeStruct((bh, sk, dp),
                                 k.dtype if g == 1 else jnp.float32),
            jax.ShapeDtypeStruct((bh, sk, dp),
                                 v.dtype if g == 1 else jnp.float32),
        ),
        grid=(bh, sk // kt2),
        in_specs=[
            smem((1, 1), lambda i, j: (0, 0)),
            smem((1, 1), lambda i, j: (0, 0)),
            vmem((1, sq, dp), lambda i, j: (i, 0, 0)),
            vmem((1, kt2, dp), lambda i, j: (kv_row(i), j, 0)),
            vmem((1, kt2, dp), lambda i, j: (kv_row(i), j, 0)),
            vmem((1, sq, dp), lambda i, j: (i, 0, 0)),
            vmem((1, n_q2, _SUBLANES, qt2), lambda i, j: (i, 0, 0, 0)),
            vmem((1, n_q2, _SUBLANES, qt2), lambda i, j: (i, 0, 0, 0)),
        ],
        out_specs=(
            vmem((1, kt2, dp), lambda i, j: (i, j, 0)),
            vmem((1, kt2, dp), lambda i, j: (i, j, 0)),
        ),
        compiler_params=_compiler_params(vmem_dkv),
        interpret=interpret,
        name=KERNEL_NAMES[2],
    )(qoff, kvoff, qb, kb, vb, dob, rows(lse_s), rows(dd_s))
    if g == 1:
        dk, dv = dk_p, dv_p
    else:
        def gsum(p, dtype):
            p = p.reshape(b, h_kv, g, sk, dp).sum(axis=2)
            return p.reshape(b * h_kv, sk, dp).astype(dtype)
        dk, dv = gsum(dk_p, k.dtype), gsum(dv_p, v.dtype)

    return _from_bh(dq, b, d), _from_bh(dk, b, d), _from_bh(dv, b, d)


def _bwd_eligible(q, k) -> bool:
    """The bwd kernels additionally stage, per grid step of the dkv
    kernel, full-length q+do plus the two row-statistic arrays (lse, dd)
    — :func:`tile_plan` counts them and has ``dq`` / ``dkv`` tiles only
    where they fit.  f64 (the x64 CPU oracle suite) never takes the
    kernel."""
    if q.dtype not in (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16)):
        return False
    return tile_plan(q.shape[1], k.shape[1], q.shape[3], k.dtype).dkv \
        is not None


# ---------------------------------------------------------------------------
# Differentiable public entry
# ---------------------------------------------------------------------------


def _block_fwd_dispatch(q, k, v, q_off, kv_off, causal: bool, impl: str,
                        window: int = 0):
    if impl == "jnp":
        return _jnp_block(q, k, v, q_off, kv_off, causal, window)
    if impl == "pallas":
        if not _eligible(q, k):
            raise ValueError(
                f"impl='pallas' requires kernel-eligible shapes "
                f"(head_dim >= 64, tile-divisible sequence lengths, KV "
                f"block within the VMEM budget); got q{q.shape} "
                f"k{k.shape} — use impl='auto' to fall back to jnp")
        return _pallas_block(q, k, v, q_off, kv_off, causal,
                             interpret=not _on_tpu(), window=window)
    # auto: the dispatch is the stated predicate and nothing else.  On a
    # TPU an eligible shape IS the kernel; if Mosaic rejects it, the
    # enclosing program's compile raises with Mosaic's own message —
    # a training run must never lose its kernel silently.
    if _eligible(q, k) and _on_tpu():
        return _pallas_block(q, k, v, q_off, kv_off, causal,
                             interpret=False, window=window)
    return _jnp_block(q, k, v, q_off, kv_off, causal, window)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _block(q, k, v, q_off, kv_off, causal: bool, impl: str,
           window: int = 0):
    return _block_fwd_dispatch(q, k, v, q_off, kv_off, causal, impl,
                               window)


def _block_fwd(q, k, v, q_off, kv_off, causal, impl, window=0):
    out, lse = _block_fwd_dispatch(q, k, v, q_off, kv_off, causal, impl,
                                   window)
    # Named HERE, before the pair is both output and residual: a name put
    # on the output outside the custom_vjp would save a copy of ``out``
    # and still rerun the kernel for ``lse``.
    out = checkpoint_name(out, "flash_out")
    lse = checkpoint_name(lse, "flash_lse")
    return (out, lse), (q, k, v, q_off, kv_off, out, lse)


# Backward recomputation is KV-tiled beyond this many keys so the rebuilt
# score slab stays (b, sq, h, _MIN_TILE) instead of (b, sq, h, sk) — the
# memory the fused forward saves must not reappear transiently in HBM on
# the way back.  Small blocks keep the one-shot einsum (fewer reassociated
# sums: the x64 oracle tests compare at 1e-12).
_BWD_TILE_ABOVE = 512


def _bwd_tile_math(qf, k_tile, v_tile, do, lse, delta, dlse, q_pos,
                   kv_pos_tile, causal, scale, window, prec):
    """Gradient contributions of one KV tile (shared by the one-shot and
    tiled paths; flash backward: ds = p * (dp - delta + dlse))."""
    s = jnp.einsum("bqhd,bkhd->bqhk", qf, k_tile, precision=prec) * scale
    if causal:
        m2 = q_pos[:, None] >= kv_pos_tile[None, :]
        if window:
            m2 &= (q_pos[:, None] - kv_pos_tile[None, :]) < window
        mask = m2[None, :, None, :]
        s = jnp.where(mask, s, NEG_BIG)
    p = jnp.exp(s - lse[..., None])          # = softmax over this block
    if causal:
        p = jnp.where(mask, p, 0.0)
    dp = jnp.einsum("bqhd,bkhd->bqhk", do, v_tile, precision=prec)
    dv = jnp.einsum("bqhk,bqhd->bkhd", p, do, precision=prec)
    ds = p * (dp - delta[..., None] + dlse[..., None])
    dq = jnp.einsum("bqhk,bkhd->bqhd", ds, k_tile, precision=prec) * scale
    dk = jnp.einsum("bqhk,bqhd->bkhd", ds, qf, precision=prec) * scale
    return dq, dk, dv


def _zero_offsets(q_off):
    """Offsets are integer primals: their cotangent type is float0 (the
    symbolic-zero tangent dtype JAX mandates for non-inexact inputs)."""
    import numpy as np

    return np.zeros(jnp.shape(q_off), jax.dtypes.float0)


def _block_bwd(causal, impl, window, res, cot):
    """Flash-style backward by block recomputation (residuals: out + lse;
    the score matrix is rebuilt — never stored).  Dispatch mirrors the
    forward: the fused Pallas dq/dk/dv kernels on eligible TPU shapes
    (``_bwd_eligible``, no compile probe — a Mosaic failure raises),
    tiled jnp otherwise — the jnp path is the oracle the kernels are
    tested against."""
    q, k, v, q_off, kv_off, out, lse = res
    do, dlse = cot

    use_kernel, interpret = False, False
    if impl == "pallas":
        use_kernel = _bwd_eligible(q, k)
        interpret = not _on_tpu()
    elif impl == "auto":
        use_kernel = _bwd_eligible(q, k) and _on_tpu()
    if use_kernel:
        delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                        axis=-1)                          # (b, sq, h)
        dd = delta - dlse.astype(jnp.float32)
        dq, dk, dv = _pallas_bwd(q, k, v, do, lse, dd, q_off, kv_off,
                                 causal, interpret, window)
        zero_off = _zero_offsets(q_off)
        return dq, dk, dv, zero_off, zero_off

    f32 = _compute_dtype(q)
    b, sq, h, d = q.shape
    sk, h_kv = k.shape[1], k.shape[2]
    g = _gqa_groups(q, k)
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, f32))
    qf, kf, vf = q.astype(f32), k.astype(f32), v.astype(f32)
    # GQA on the oracle path: compute as MHA against repeated KV, then
    # sum each group's dk/dv back onto its shared KV head at the end.
    kf, vf = _group_repeat_kv(kf, g), _group_repeat_kv(vf, g)
    do = do.astype(f32)
    lse = lse.astype(f32)
    dlse = dlse.astype(f32)
    delta = jnp.sum(do * out.astype(f32), axis=-1)      # (b, q, h)
    q_pos = q_off + jnp.arange(sq, dtype=jnp.int32)
    kv_pos = kv_off + jnp.arange(sk, dtype=jnp.int32)

    kt = _MIN_TILE
    prec = dot_precision(q.dtype)
    if sk <= _BWD_TILE_ABOVE or sk % kt != 0:
        dq, dk, dv = _bwd_tile_math(qf, kf, vf, do, lse, delta, dlse,
                                    q_pos, kv_pos, causal, scale, window,
                                    prec)
    else:
        def body(j, carry):
            dq, dk, dv = carry
            k_t = jax.lax.dynamic_slice_in_dim(kf, j * kt, kt, 1)
            v_t = jax.lax.dynamic_slice_in_dim(vf, j * kt, kt, 1)
            kv_pos_t = jax.lax.dynamic_slice_in_dim(kv_pos, j * kt, kt, 0)
            dq_t, dk_t, dv_t = _bwd_tile_math(
                qf, k_t, v_t, do, lse, delta, dlse, q_pos, kv_pos_t,
                causal, scale, window, prec)
            dq = dq + dq_t
            dk = jax.lax.dynamic_update_slice_in_dim(dk, dk_t, j * kt, 1)
            dv = jax.lax.dynamic_update_slice_in_dim(dv, dv_t, j * kt, 1)
            return dq, dk, dv

        dq, dk, dv = jax.lax.fori_loop(
            0, sk // kt, body,
            (jnp.zeros_like(qf), jnp.zeros_like(kf), jnp.zeros_like(vf)))

    dk, dv = _group_sum(dk, b, h_kv, g), _group_sum(dv, b, h_kv, g)
    zero_off = _zero_offsets(q_off)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            zero_off, zero_off)


_block.defvjp(_block_fwd, _block_bwd)


def flash_block_attention(q, k, v, *, causal: bool = False, q_offset=0,
                          kv_offset=0, impl: str = "auto",
                          window: int = 0
                          ) -> Tuple[jax.Array, jax.Array]:
    """Normalized attention partials of ``q`` against one KV block.

    Args are ``(batch, seq, heads, head_dim)``.  Grouped-query attention:
    ``k``/``v`` may carry fewer heads than ``q`` (any divisor); q head
    ``h`` attends through KV head ``h // (h_q // h_kv)``.  The Pallas
    kernels resolve the grouping in their KV BlockSpec index maps (KV is
    never duplicated in HBM); the jnp path realizes it by KV repeat (it
    is the memory-unconstrained oracle).  Offsets are the *integer*
    global positions of the first query/key (may be traced; exact to
    2^31-1 — float inputs are truncated to int32, losing exactness past
    2^24 before the cast).  Returns
    ``(out, lse)`` with ``out`` of ``q``'s shape/dtype and ``lse`` of shape
    ``(batch, seq_q, heads)`` in the compute dtype (f32, or f64 under x64
    on the jnp path).  ``impl``: ``"auto"`` (Pallas on
    eligible TPU shapes, else jnp), ``"pallas"`` (forced; interpreted off
    TPU — for tests), ``"jnp"``.

    ``window > 0`` (requires ``causal``) restricts each query to its last
    ``window`` positions, itself included — sliding-window/local
    attention.  The kernels skip KV tiles on BOTH sides of the live band
    (the causal diagonal above, the window frontier below), so compute
    per q tile is O(window) regardless of sequence length; masking is
    global-position-based, so windows span block boundaries under ring
    attention exactly."""
    if impl not in ("auto", "pallas", "jnp"):
        raise ValueError(f"unknown impl {impl!r}")
    if k.shape != v.shape or q.shape[0] != k.shape[0] \
            or q.shape[3] != k.shape[3]:
        raise ValueError(
            f"q{q.shape} and k{k.shape}/v{v.shape} must agree on batch "
            f"and head_dim, and k/v must match")
    if q.shape[2] % k.shape[2] != 0:
        raise ValueError(
            f"query heads ({q.shape[2]}) must be a multiple of KV heads "
            f"({k.shape[2]}) — grouped-query attention maps q head h to "
            f"KV head h // (h_q // h_kv)")
    if window < 0:
        raise ValueError(f"window must be >= 0, got {window}")
    if window and not causal:
        raise ValueError(
            "window > 0 requires causal=True (sliding-window attention "
            "is defined over the causal mask)")
    q_off = jnp.asarray(q_offset, jnp.int32)
    kv_off = jnp.asarray(kv_offset, jnp.int32)
    if q_off.ndim > 0 or kv_off.ndim > 0:
        # Per-row offsets (shape ``(batch,)``): the continuous-batching
        # decode path of mpi4torch_tpu.serve, where every slot of the
        # batch sits at its own position.  jnp-only (the kernels key
        # their tile skipping off ONE scalar frontier) and forward-only
        # — serving decode never differentiates.
        for name, off in (("q_offset", q_off), ("kv_offset", kv_off)):
            if off.ndim > 1 or (off.ndim == 1
                                and off.shape[0] != q.shape[0]):
                raise ValueError(
                    f"{name} must be a scalar or a (batch,) vector of "
                    f"per-row positions; got shape {off.shape} for "
                    f"batch {q.shape[0]}")
        if impl == "pallas":
            raise ValueError(
                "per-row q_offset/kv_offset vectors ride the jnp path "
                "only (the Pallas kernels tile-skip off one scalar "
                "frontier); use impl='jnp' or 'auto'")
        impl = "jnp"
    return _block(q, k, v, q_off, kv_off, causal, impl, window)


def merge_partials(out_a, lse_a, out_b, lse_b):
    """Exact merge of two normalized attention partials over disjoint KV
    sets — the online-softmax combination rule (associative and, in exact
    arithmetic, commutative)."""
    ct = _compute_dtype(out_a)
    lse = jnp.logaddexp(lse_a, lse_b)
    wa = jnp.exp(lse_a - lse).astype(ct)[..., None]
    wb = jnp.exp(lse_b - lse).astype(ct)[..., None]
    out = out_a.astype(ct) * wa + out_b.astype(ct) * wb
    return out.astype(out_a.dtype), lse


def _kv_chunk_for(q, k) -> int:
    """Largest KV-chunk length that (a) divides the sequence, (b) is a
    whole number of KV tiles, and (c) fits the kernel's VMEM staging
    budget — or 0 when chunking cannot make the shape eligible (head dim
    too small, non-tile-divisible lengths; the caller then falls back to
    one unchunked call and its usual dispatch).  Pure integer arithmetic:
    shapes are static, so this runs once per trace.

    Backward eligibility is deliberately NOT required: an ineligible
    backward falls back per block to the KV-tiled jnp recompute, whose
    transient slab is (b, sq, h, 128) — chunking still removes the
    quadratic forward memory either way."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    # What does not depend on the chunk (head size, a q tile that
    # divides) the plan answers for the narrowest one.
    if sk % _MIN_TILE != 0 \
            or tile_plan(sq, _MIN_TILE, d, k.dtype).fwd is None:
        return 0
    per_token = 2 * _lane_pad(d) * jnp.dtype(k.dtype).itemsize
    chunk = min((_KV_VMEM_BUDGET // per_token) // _MIN_TILE * _MIN_TILE, sk)
    while sk % chunk != 0:
        chunk -= _MIN_TILE
    return chunk


def flash_attention(q, k, v, *, causal: bool = False, impl: str = "auto",
                    kv_chunk: int = 0, window: int = 0):
    """Single-device fused attention over the full local KV (the
    non-distributed entry; ``parallel.ring_attention`` composes the block
    primitive over a mesh axis instead).

    Long-KV path: the block kernel stages its whole KV block in VMEM, so
    one call caps the sequence at the VMEM budget (8K tokens at
    d=128/f32, 16K at bf16).  Beyond that — e.g. the full global sequence each rank
    sees after the Ulysses reshuffle — the KV is processed in
    budget-sized chunks under ``lax.scan``, each through the fused
    kernel, merged by the exact online-softmax rule (the same
    ``merge_partials`` ring attention uses), so memory stays
    O(seq + chunks x q) instead of the jnp fallback's quadratic score
    matrix.  ``kv_chunk`` forces a chunk length (must divide the KV
    length and be a multiple of the 128-key tile floor); 0 picks the largest
    eligible chunk automatically, and shapes with no eligible chunk take
    the ordinary single-call dispatch."""
    sk = k.shape[1]
    if kv_chunk:
        # The kernel path needs whole KV tiles per chunk; the jnp path
        # merges any divisor (useful for testing the merge math).
        if kv_chunk < 0 or sk % kv_chunk != 0 or (
                impl != "jnp" and kv_chunk % _MIN_TILE != 0):
            raise ValueError(
                f"kv_chunk={kv_chunk} must divide the KV length {sk} and "
                f"(for kernel paths) be a multiple of {_MIN_TILE}")
        chunk = kv_chunk
    elif impl != "jnp" and not _eligible(q, k):
        chunk = _kv_chunk_for(q, k)
    else:
        chunk = 0

    if chunk == 0 or chunk == sk:
        out, _ = flash_block_attention(q, k, v, causal=causal, impl=impl,
                                       window=window)
        return out

    n_chunks = sk // chunk

    def body(carry, i):
        out, lse = carry
        # Slice chunks in place — stacking a transposed (n_chunks, ...)
        # copy would transiently double KV HBM on exactly the
        # long-context path this exists to keep linear.
        k_c = jax.lax.dynamic_slice_in_dim(k, i * chunk, chunk, 1)
        v_c = jax.lax.dynamic_slice_in_dim(v, i * chunk, chunk, 1)
        o_b, lse_b = flash_block_attention(
            q, k_c, v_c, causal=causal, kv_offset=i * chunk, impl=impl,
            window=window)
        out, lse = merge_partials(out, lse, o_b, lse_b)
        return (out, lse), None

    out0 = jnp.zeros_like(q)
    lse0 = jnp.full((q.shape[0], q.shape[1], q.shape[2]), NEG_BIG,
                    _compute_dtype(q))
    (out, _), _ = jax.lax.scan(
        body, (out0, lse0), jnp.arange(n_chunks, dtype=jnp.int32))
    return out
