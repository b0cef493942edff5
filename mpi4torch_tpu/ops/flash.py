"""Fused block attention (flash-style) — the TPU hot-op kernel.

The ring/dense attention in ``parallel.attention`` is algebraically a
sequence of *block attention* calls merged by an online softmax.  This
module provides that block primitive two ways behind one signature:

* a Pallas TPU kernel (`pltpu`): q tiles stream through VMEM, the KV loop
  runs fused in-core (scores, masking, online softmax, PV accumulation all
  without materializing the (q, k) score matrix in HBM), MXU matmuls in
  f32 accumulation;
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
from typing import Tuple

import jax
import jax.numpy as jnp

NEG_BIG = -1e30
_Q_TILE = 128
_KV_TILE = 128
# Per-row statistics (lse, and the backward's delta/dlse) cross the
# kernel boundary broadcast along a full lane tile: a (qt,) vector in
# sublane orientation cannot be stored to / loaded from a lane-oriented
# row without a relayout Mosaic may reject, so the stats ride as
# (rows, 128) with the value replicated across lanes — the layout jax's
# own TPU flash kernel uses (MIN_BLOCK_SIZE in
# jax/experimental/pallas/ops/tpu/flash_attention.py).
_STAT_LANES = 128


# The kernel stages the whole KV block in VMEM per grid step (the KV loop
# runs in-core); cap the staged bytes well under the ~16 MB/core VMEM so
# q tiles, outputs and accumulators still fit.  Longer local blocks fall
# back to the jnp path (ring attention keeps per-rank blocks short anyway).
_KV_VMEM_BUDGET = 8 * 1024 * 1024

# Stable names of the three Mosaic kernels (forward, backward dq,
# backward dk/dv): what a lowered program's ``kernel_name`` attributes
# and a profiler trace's kernel events are matched against.
KERNEL_NAMES = ("mpi4torch_flash_fwd", "mpi4torch_flash_bwd_dq",
                "mpi4torch_flash_bwd_dkv")


def _lane_pad(d: int) -> int:
    """Head dim as staged in VMEM: the next lane multiple (128)."""
    return 128 * ((d + 127) // 128)


def _eligible(q, k) -> bool:
    """Shapes the TPU kernel handles: sequence lengths divisible by their
    tile and the staged KV within the VMEM budget.  head_dim need not be
    a lane multiple — the kernel zero-pads it to the next multiple of 128
    (d=64/96 pay ≤2x staged bytes, still far cheaper than the jnp path's
    HBM score matrix).  d < 64 would waste >2x MXU/VMEM on padding, so
    those shapes take the jnp fallback (XLA fuses them fine)."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    if d < 64:
        return False
    if 2 * sk * _lane_pad(d) * jnp.dtype(k.dtype).itemsize > _KV_VMEM_BUDGET:
        return False
    qt = min(_Q_TILE, sq)
    kt = min(_KV_TILE, sk)
    return sq % qt == 0 and sk % kt == 0


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
        s = jnp.where(bmask, s, NEG_BIG)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    if causal:
        p = jnp.where(bmask, p, 0.0)
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bqhk,bkhd->bqhd", p, v.astype(ct), precision=prec)
    safe_l = jnp.where(l > 0, l, 1.0)
    out = jnp.where(l[..., None] > 0, acc / safe_l[..., None], 0.0)
    lse = jnp.where(l > 0, m + jnp.log(safe_l), NEG_BIG)
    return out.astype(q.dtype), lse


# ---------------------------------------------------------------------------
# Pallas TPU kernel
# ---------------------------------------------------------------------------


def _causal_n_live(qoff, kvoff, qi, qt: int, kv_tile: int, n_tiles: int):
    """Number of leading KV tiles that can contain unmasked positions for
    q tile ``qi``: tiles whose first position <= this q tile's LAST
    position (q_hi).  Skipped tiles are exactly neutral in both the
    online-softmax carry and the gradients (p is where-masked to zero),
    so cutting the loop at the diagonal halves causal work without
    changing any output bit.  Traced-scalar offsets (rank-symbolic under
    SPMD) are fine: the bound feeds a dynamic fori_loop."""
    q_hi = qoff + (qi + 1) * qt - 1
    return jnp.clip((q_hi - kvoff) // kv_tile + 1, 0, n_tiles)


def _window_start_tile(qoff, kvoff, qi, qt: int, kv_tile: int,
                       window: int, n_tiles: int):
    """First KV tile that can contain in-window positions for q tile
    ``qi`` under a sliding window: the tile holding position
    ``q_lo - window + 1`` (this q tile's FIRST query's earliest visible
    key).  Earlier tiles are fully below every query's window — skipping
    them makes windowed attention cost O(window), not O(seq), per query
    tile.  Same exact-neutrality argument as :func:`_causal_n_live`."""
    q_lo = qoff + qi * qt
    return jnp.clip((q_lo - window + 1 - kvoff) // kv_tile, 0, n_tiles)


def _parallel_grid_params():
    """Shared CompilerParams for all three kernels: both grid dims are
    fully independent (each step writes a distinct output block; all
    reduction lives in in-core fori_loops), so Mosaic may pipeline the
    grid and split it across cores on megacore parts."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel"))


def _fwd_kernel(qoff_ref, kvoff_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                *, causal: bool, kv_tile: int, true_d: int,
                window: int = 0):
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    i32 = jnp.int32
    qt, d = q_ref.shape[1], q_ref.shape[2]
    sk = k_ref.shape[1]
    n_kv = sk // kv_tile
    # d is the lane-padded staging width; the softmax scale is the model's
    # true head_dim (padded columns are zero and change no dot product).
    scale = 1.0 / jnp.sqrt(jnp.asarray(true_d, f32))

    # Operands stay in their input dtype for the MXU dots (bf16 inputs
    # run at the MXU's bf16 rate; an up-front astype(f32) would force
    # f32-rate multiplies) — accumulation is f32 via
    # preferred_element_type, and the scale is applied to the f32 scores.
    # f32 operands pin the f32-exact contract (see dot_precision).
    prec = dot_precision(q_ref.dtype)
    qb = q_ref[0]                                           # (QT, D)
    qi = pl.program_id(1)
    q_pos = (qoff_ref[0, 0] + qi * qt
             + jax.lax.broadcasted_iota(i32, (qt, 1), 0))    # (QT, 1)

    def body(j, carry):
        m, l, acc = carry
        kb = k_ref[0, pl.ds(j * kv_tile, kv_tile), :]
        vb = v_ref[0, pl.ds(j * kv_tile, kv_tile), :]
        s = jax.lax.dot_general(
            qb, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=f32, precision=prec) * scale  # (QT, KT)
        if causal:
            kv_pos = (kvoff_ref[0, 0] + j * kv_tile
                      + jax.lax.broadcasted_iota(i32, (1, kv_tile), 1))
            mask = q_pos >= kv_pos                           # (QT, KT)
            if window:
                mask &= (q_pos - kv_pos) < window
            s = jnp.where(mask, s, NEG_BIG)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        if causal:
            p = jnp.where(mask, p, 0.0)
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * corr + jax.lax.dot_general(
            p.astype(vb.dtype), vb, (((1,), (0,)), ((), ())),
            preferred_element_type=f32, precision=prec)
        return m_new, l, acc

    m0 = jnp.full((qt, 1), NEG_BIG, f32)
    l0 = jnp.zeros((qt, 1), f32)
    acc0 = jnp.zeros((qt, d), f32)
    n_live = (_causal_n_live(qoff_ref[0, 0], kvoff_ref[0, 0], qi, qt,
                             kv_tile, n_kv) if causal else n_kv)
    j0 = (_window_start_tile(qoff_ref[0, 0], kvoff_ref[0, 0], qi, qt,
                             kv_tile, window, n_kv)
          if (causal and window) else 0)
    m, l, acc = jax.lax.fori_loop(j0, n_live, body, (m0, l0, acc0))

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


def _pallas_block(q, k, v, q_off, kv_off, causal: bool, interpret: bool,
                  window: int = 0):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, sq, h, d = q.shape
    sk, h_kv = k.shape[1], k.shape[2]
    g = _gqa_groups(q, k)
    bh = b * h
    qt = min(_Q_TILE, sq)
    kt = min(_KV_TILE, sk)
    dp = _lane_pad(d)

    def to_bh(x, s, nh):
        x = x.transpose(0, 2, 1, 3).reshape(b * nh, s, d)
        if dp != d:
            # Zero-pad head_dim to the lane width.  Zeros leave every dot
            # product unchanged (scores and PV columns), so only the
            # output slice below is needed to undo it.
            x = jnp.pad(x, ((0, 0), (0, 0), (0, dp - d)))
        return x

    kv_row = functools.partial(_kv_row, h=h, h_kv=h_kv, g=g)

    qb = to_bh(q, sq, h)
    kb, vb = to_bh(k, sk, h_kv), to_bh(v, sk, h_kv)
    qoff = jnp.asarray(q_off, jnp.int32).reshape(1, 1)
    kvoff = jnp.asarray(kv_off, jnp.int32).reshape(1, 1)

    grid = (bh, sq // qt)
    smem = functools.partial(pl.BlockSpec, memory_space=pltpu.SMEM)
    vmem = functools.partial(pl.BlockSpec, memory_space=pltpu.VMEM)
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, causal=causal, kv_tile=kt,
                          true_d=d, window=window),
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
        grid=grid,
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
        compiler_params=_parallel_grid_params(),
        interpret=interpret,
        name=KERNEL_NAMES[0],
    )(qoff, kvoff, qb, kb, vb)

    if dp != d:
        out = out[:, :, :d]
    out = out.reshape(b, h, sq, d).transpose(0, 2, 1, 3)
    lse = lse[:, :, 0].reshape(b, h, sq).transpose(0, 2, 1)
    return out, lse


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


def _stat_tile(x, width: int):
    """Resize a (rows, _STAT_LANES) lane-broadcast statistic to (rows,
    width) without relayout.  Every lane holds the same value, so
    narrower widths are a leading-lane slice and wider widths (KV tiles
    above 128 — the tunable `_KV_TILE`) are a relayout-free
    lane-tiling concat of the
    already-broadcast slab."""
    if width == _STAT_LANES:
        return x
    if width < _STAT_LANES:
        return x[:, :width]
    reps = -(-width // _STAT_LANES)
    return jnp.concatenate([x] * reps, axis=1)[:, :width]


def _bwd_p_ds(q_t, k_t, v_t, do_t, lse_t, dd_t, q_pos, kv_pos,
              causal: bool, scale, window, prec):
    """Recompute p and ds for one (q-tile, kv-tile) pair, in-kernel.

    ``lse`` and ``dd = delta - dlse`` arrive as (QT, KT) lane-broadcast
    tiles (see _STAT_LANES); fusing delta and dlse into one stat array
    saves a third of the staged stat VMEM (they only ever appear as this
    difference: ds = p*(dp - delta + dlse)).  The dlse term is live under
    ring attention, whose merge consumes lse.  Fully-masked rows have
    lse = NEG_BIG, making the raw exp() garbage; the mask ``where``
    zeroes those entries (same order of operations as the jnp oracle)."""
    f32 = jnp.float32
    # Native-dtype MXU operands, f32 accumulation (see _fwd_kernel).
    s = jax.lax.dot_general(q_t, k_t, (((1,), (1,)), ((), ())),
                            preferred_element_type=f32,
                            precision=prec) * scale               # (QT, KT)
    p = jnp.exp(s - lse_t)
    if causal:
        mask = q_pos >= kv_pos                                    # (QT, KT)
        if window:
            mask &= (q_pos - kv_pos) < window
        p = jnp.where(mask, p, 0.0)
    dp_ = jax.lax.dot_general(do_t, v_t, (((1,), (1,)), ((), ())),
                              preferred_element_type=f32, precision=prec)
    ds = p * (dp_ - dd_t)
    return p, ds


def _bwd_dq_kernel(qoff_ref, kvoff_ref, q_ref, k_ref, v_ref, do_ref,
                   lse_ref, dd_ref, dq_ref,
                   *, causal: bool, kv_tile: int, true_d: int,
                   window: int = 0):
    from jax.experimental import pallas as pl

    f32, i32 = jnp.float32, jnp.int32
    qt, d = q_ref.shape[1], q_ref.shape[2]
    sk = k_ref.shape[1]
    scale = 1.0 / jnp.sqrt(jnp.asarray(true_d, f32))
    prec = dot_precision(q_ref.dtype)

    qb = q_ref[0]
    dob = do_ref[0]
    lse_t = _stat_tile(lse_ref[0], kv_tile)
    dd_t = _stat_tile(dd_ref[0], kv_tile)
    qi = pl.program_id(1)
    q_pos = (qoff_ref[0, 0] + qi * qt
             + jax.lax.broadcasted_iota(i32, (qt, 1), 0))

    def body(j, dq):
        kb = k_ref[0, pl.ds(j * kv_tile, kv_tile), :]
        vb = v_ref[0, pl.ds(j * kv_tile, kv_tile), :]
        kv_pos = (kvoff_ref[0, 0] + j * kv_tile
                  + jax.lax.broadcasted_iota(i32, (1, kv_tile), 1))
        _, ds = _bwd_p_ds(qb, kb, vb, dob, lse_t, dd_t,
                          q_pos, kv_pos, causal, scale, window, prec)
        return dq + jax.lax.dot_general(
            ds.astype(kb.dtype), kb, (((1,), (0,)), ((), ())),
            preferred_element_type=f32, precision=prec) * scale

    n_kv = sk // kv_tile
    n_live = (_causal_n_live(qoff_ref[0, 0], kvoff_ref[0, 0], qi, qt,
                             kv_tile, n_kv) if causal else n_kv)
    j0 = (_window_start_tile(qoff_ref[0, 0], kvoff_ref[0, 0], qi, qt,
                             kv_tile, window, n_kv)
          if (causal and window) else 0)
    dq = jax.lax.fori_loop(j0, n_live, body, jnp.zeros((qt, d), f32))
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _bwd_dkv_kernel(qoff_ref, kvoff_ref, q_ref, k_ref, v_ref, do_ref,
                    lse_ref, dd_ref, dk_ref, dv_ref,
                    *, causal: bool, q_tile: int, true_d: int,
                    window: int = 0):
    from jax.experimental import pallas as pl

    f32, i32 = jnp.float32, jnp.int32
    kt, d = k_ref.shape[1], k_ref.shape[2]
    sq = q_ref.shape[1]
    scale = 1.0 / jnp.sqrt(jnp.asarray(true_d, f32))
    prec = dot_precision(q_ref.dtype)

    kb = k_ref[0]
    vb = v_ref[0]
    ki = pl.program_id(1)
    kv_pos = (kvoff_ref[0, 0] + ki * kt
              + jax.lax.broadcasted_iota(i32, (1, kt), 1))

    def body(i, carry):
        dk, dv = carry
        qs = pl.ds(i * q_tile, q_tile)
        q_t = q_ref[0, qs, :]
        do_t = do_ref[0, qs, :]
        lse_t = _stat_tile(lse_ref[0, qs, :], kt)
        dd_t = _stat_tile(dd_ref[0, qs, :], kt)
        q_pos = (qoff_ref[0, 0] + i * q_tile
                 + jax.lax.broadcasted_iota(i32, (q_tile, 1), 0))
        p, ds = _bwd_p_ds(q_t, kb, vb, do_t, lse_t, dd_t,
                          q_pos, kv_pos, causal, scale, window, prec)
        dv = dv + jax.lax.dot_general(
            p.astype(do_t.dtype), do_t, (((0,), (0,)), ((), ())),
            preferred_element_type=f32, precision=prec)    # (KT, D)
        dk = dk + jax.lax.dot_general(
            ds.astype(q_t.dtype), q_t, (((0,), (0,)), ((), ())),
            preferred_element_type=f32, precision=prec) * scale
        return dk, dv

    dk0 = jnp.zeros((kt, d), f32)
    n_q = sq // q_tile
    if causal:
        # Mirror cut: q tile i contributes iff its last position reaches
        # this KV block's first position — start the loop at the
        # diagonal.  i_min = floor((kv_lo - qoff) / q_tile) (clipped), the
        # first tile whose max q_pos >= kv_lo.
        kv_lo = kvoff_ref[0, 0] + ki * kt
        i_start = jnp.clip((kv_lo - qoff_ref[0, 0]) // q_tile, 0, n_q)
    else:
        i_start = 0
    if causal and window:
        # Window mirror cut: the farthest query still inside any of this
        # KV tile's windows sits at kv_hi + window - 1 — stop after its
        # tile.
        kv_hi = kvoff_ref[0, 0] + (ki + 1) * kt - 1
        i_end = jnp.clip((kv_hi + window - 1 - qoff_ref[0, 0]) // q_tile
                         + 1, 0, n_q)
    else:
        i_end = n_q
    dk, dv = jax.lax.fori_loop(i_start, i_end, body, (dk0, dk0))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _pallas_bwd(q, k, v, do, lse, dd, q_off, kv_off,
                causal: bool, interpret: bool, window: int = 0):
    """Fused dq/dk/dv.  Layout/staging mirrors ``_pallas_block``; the row
    statistics (lse, delta, dlse) ride lane-broadcast as
    (bh, sq, _STAT_LANES) f32 — the same Mosaic-proven scheme as the
    forward's lse output."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, sq, h, d = q.shape
    sk, h_kv = k.shape[1], k.shape[2]
    g = _gqa_groups(q, k)
    bh = b * h
    qt = min(_Q_TILE, sq)
    kt = min(_KV_TILE, sk)
    dp = _lane_pad(d)

    def to_bh(x, s, nh):
        x = x.transpose(0, 2, 1, 3).reshape(b * nh, s, d)
        if dp != d:
            x = jnp.pad(x, ((0, 0), (0, 0), (0, dp - d)))
        return x

    kv_row = functools.partial(_kv_row, h=h, h_kv=h_kv, g=g)

    def rows(x):  # (b, sq, h) -> (bh, sq, _STAT_LANES) f32, lane-broadcast
        x = x.astype(jnp.float32).transpose(0, 2, 1).reshape(bh, sq)
        return jnp.broadcast_to(x[..., None], (bh, sq, _STAT_LANES))

    qb, dob = to_bh(q, sq, h), to_bh(do, sq, h)
    kb, vb = to_bh(k, sk, h_kv), to_bh(v, sk, h_kv)
    lse_r, dd_r = rows(lse), rows(dd)
    qoff = jnp.asarray(q_off, jnp.int32).reshape(1, 1)
    kvoff = jnp.asarray(kv_off, jnp.int32).reshape(1, 1)

    smem = functools.partial(pl.BlockSpec, memory_space=pltpu.SMEM)
    vmem = functools.partial(pl.BlockSpec, memory_space=pltpu.VMEM)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, causal=causal, kv_tile=kt,
                          true_d=d, window=window),
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
        compiler_params=_parallel_grid_params(),
        interpret=interpret,
        name=KERNEL_NAMES[1],
    )(qoff, kvoff, qb, kb, vb, dob, lse_r, dd_r)

    # Under GQA (g > 1) the dkv grid still walks q heads: each grid row
    # reads its shared KV head (kv_row) and writes a PER-Q-HEAD partial;
    # the g partials per KV head are summed outside the kernel.  Partials
    # are f32 so the cross-group sum accumulates at the same precision as
    # the in-kernel fori_loop (transient cost: g x f32 dk/dv, freed by
    # the sum — KV itself is still never duplicated).
    dk_p, dv_p = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, causal=causal, q_tile=qt,
                          true_d=d, window=window),
        out_shape=(
            jax.ShapeDtypeStruct((bh, sk, dp),
                                 k.dtype if g == 1 else jnp.float32),
            jax.ShapeDtypeStruct((bh, sk, dp),
                                 v.dtype if g == 1 else jnp.float32),
        ),
        grid=(bh, sk // kt),
        in_specs=[
            smem((1, 1), lambda i, j: (0, 0)),
            smem((1, 1), lambda i, j: (0, 0)),
            vmem((1, sq, dp), lambda i, j: (i, 0, 0)),
            vmem((1, kt, dp), lambda i, j: (kv_row(i), j, 0)),
            vmem((1, kt, dp), lambda i, j: (kv_row(i), j, 0)),
            vmem((1, sq, dp), lambda i, j: (i, 0, 0)),
            vmem((1, sq, _STAT_LANES), lambda i, j: (i, 0, 0)),
            vmem((1, sq, _STAT_LANES), lambda i, j: (i, 0, 0)),
        ],
        out_specs=(
            vmem((1, kt, dp), lambda i, j: (i, j, 0)),
            vmem((1, kt, dp), lambda i, j: (i, j, 0)),
        ),
        compiler_params=_parallel_grid_params(),
        interpret=interpret,
        name=KERNEL_NAMES[2],
    )(qoff, kvoff, qb, kb, vb, dob, lse_r, dd_r)
    if g == 1:
        dk, dv = dk_p, dv_p
    else:
        def gsum(p, dtype):
            p = p.reshape(b, h_kv, g, sk, dp).sum(axis=2)
            return p.reshape(b * h_kv, sk, dp).astype(dtype)
        dk, dv = gsum(dk_p, k.dtype), gsum(dv_p, v.dtype)

    def from_bh(x, s, nh):
        if dp != d:
            x = x[:, :, :d]
        return x.reshape(b, nh, s, d).transpose(0, 2, 1, 3)

    return (from_bh(dq, sq, h), from_bh(dk, sk, h_kv),
            from_bh(dv, sk, h_kv))


def _bwd_eligible(q, k) -> bool:
    """The bwd kernels additionally stage, per grid step of the dkv
    kernel, full-length q+do plus the two (sq, _STAT_LANES) f32 row-stat
    arrays (lse, dd) — all of which must fit the budget together (the
    stats alone are 2x the q+do bytes at bf16/d=128, so ignoring them
    would pass shapes that blow VMEM).  f64 (the x64 CPU oracle suite)
    never takes the kernel."""
    if q.dtype not in (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16)):
        return False
    if not _eligible(q, k):
        return False
    sq = q.shape[1]
    d_stage = _lane_pad(q.shape[3])
    staged = (2 * sq * d_stage * jnp.dtype(q.dtype).itemsize
              + 2 * sq * _STAT_LANES * 4)
    return staged <= _KV_VMEM_BUDGET


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
    return (out, lse), (q, k, v, q_off, kv_off, out, lse)


# Backward recomputation is KV-tiled beyond this many keys so the rebuilt
# score slab stays (b, sq, h, _KV_TILE) instead of (b, sq, h, sk) — the
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

    kt = _KV_TILE
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
    qt = min(_Q_TILE, sq)
    if d < 64 or sq % qt != 0 or sk % _KV_TILE != 0:
        return 0
    per_token = 2 * _lane_pad(d) * jnp.dtype(k.dtype).itemsize
    chunk = min((_KV_VMEM_BUDGET // per_token) // _KV_TILE * _KV_TILE, sk)
    while chunk >= _KV_TILE and sk % chunk != 0:
        chunk -= _KV_TILE
    return chunk if chunk >= _KV_TILE else 0


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
    length and be a multiple of the 128 KV tile); 0 picks the largest
    eligible chunk automatically, and shapes with no eligible chunk take
    the ordinary single-call dispatch."""
    sk = k.shape[1]
    if kv_chunk:
        # The kernel path needs whole KV tiles per chunk; the jnp path
        # merges any divisor (useful for testing the merge math).
        if kv_chunk < 0 or sk % kv_chunk != 0 or (
                impl != "jnp" and kv_chunk % _KV_TILE != 0):
            raise ValueError(
                f"kv_chunk={kv_chunk} must divide the KV length {sk} and "
                f"(for kernel paths) be a multiple of {_KV_TILE}")
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
