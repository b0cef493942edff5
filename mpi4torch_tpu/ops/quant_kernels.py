"""Fused dequantize → accumulate → requantize hop kernels.

The in-schedule quantized collectives (compress/spmd.py) re-quantize the
running partial sum at every ring hop so the int8 payload + per-block
scales stay on the wire end-to-end with FRESH block scales per hop —
precision loss never compounds across hops (EQuARX, arXiv 2506.17615
§3.2).  Expressed op-by-op (decode → add → encode) that hop is ~six
full-size HBM round trips of the f32 partial; this module fuses it into
ONE Pallas TPU kernel pass — dequantize the arriving int8 blocks,
accumulate the local f32 contribution, reduce the fresh per-block absmax
and requantize — so the f32 partial never leaves VMEM.

The pure-jnp fallback is bit-identical to the kernel (same op sequence,
same rounding primitives) and serves three roles, mirroring the
``ops/flash.py`` pattern: the CPU/default path, the oracle the kernel is
tested against in interpret mode, and the semantics documentation.
Dispatch is governed by :func:`mpi4torch_tpu.config.quant_hop_impl`
(``"auto"``/``"jnp"``/``"pallas"``), which is part of the ``run_spmd``
jit fingerprint so toggling the knob retraces instead of silently
reusing the old lowering.

Block layout contract (shared with compress/codecs.py BlockQ8Codec):
``q`` is ``(nblocks, block)`` int8, ``scale`` is ``(nblocks,)`` f32,
``mine`` is the zero-padded f32 contribution in the same block shape.
Stochastic rounding (the ``q8_ef_hop`` codec) receives its noise as an
OPERAND — uniform [0, 1) samples generated from the schedule key outside
the kernel — so the kernel and the fallback consume identical bits and
stay bit-equal under either implementation.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .. import config as _config

# Row-block the kernel grid iterates over: 256 rows × a 256-lane block of
# f32 is 256 KiB of VMEM per operand — comfortably within budget with
# the int8/scale/noise operands alongside.
_ROW_TILE = 256


def _on_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"


def ring_salt(round_idx: int, channel: int) -> int:
    """THE salt of one quantized ring channel: round ``round_idx`` of the
    codec's error-feedback rounds, channel ``channel`` of the multipath
    schedule (0 for ``ring``; 0/1 for ``bidir``/``torus``).  One shared
    rule for the Mode A pipeline (compress/spmd.py) and the Mode B fold
    oracle (constants.reduce_q8_hop) — the two sides derive identical
    :func:`schedule_key` streams from it, which is what makes the
    stochastic ``q8_ef_hop`` codec bitwise-reproducible across modes."""
    return round_idx * 2 + channel


def chunk_blocks(flat, n: int, block: int):
    """THE chunk layout of the in-schedule quantized collectives: the
    flat f32 payload splits into ``n`` ring chunks of ``nb`` whole
    ``block``-element quantization blocks each (``nb = ceil(ceil(total /
    n) / block)``), zero-padded at the tail.  Chunk ``c`` covers flat
    elements ``[c * nb * block, (c+1) * nb * block)``; whole-block
    chunks mean per-hop requantization never mixes two chunks into one
    scale.  Returns ``(xcb, nb)`` with ``xcb`` shaped ``(n, nb,
    block)``.  Shared by compress/spmd.py and the eager fold oracle
    (constants.reduce_q8_hop) so Mode A and Mode B can never disagree
    about which element lives in which block of which chunk."""
    total = flat.size
    seg = -(-max(total, 1) // n)
    nb = -(-seg // block)
    pad = n * nb * block - total
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), jnp.float32)])
    return flat.reshape(n, nb, block), nb


def schedule_key(salt: int, hop: int, rank):
    """THE per-hop PRNG key of schedule-keyed stochastic codecs
    (``Codec.schedule_keyed``): a pure function of (salt, hop, rank) —
    no call counters, no data fingerprints — so the Mode A pipeline
    (compress/spmd.py, ``rank`` a traced ``lax.axis_index``) and the
    eager fold oracle (constants.reduce_q8_hop, ``rank`` a Python int)
    derive bit-identical noise.  One implementation for both, or the
    cross-mode bitwise-parity contract would hinge on two copies of a
    fold-in chain staying in sync."""
    key = jax.random.fold_in(jax.random.PRNGKey(0), salt)
    key = jax.random.fold_in(key, hop)
    return jax.random.fold_in(key, rank)


def hop_noise(key, nblocks: int, block: int):
    """Uniform [0, 1) stochastic-rounding noise for one hop, in the
    block shape the kernel consumes.  Generated OUTSIDE the kernel and
    passed as an operand, so the Pallas kernel and the jnp fallback see
    the exact same bits."""
    return jax.random.uniform(key, (nblocks, block), jnp.float32)


def po2_scale(amax):
    """The block-floating-point scale: the smallest power of two ``s``
    with ``127 * s >= amax`` (clamped to the smallest normal f32 for
    zero/subnormal blocks).

    A power-of-two scale makes the ENTIRE quantization arithmetic exact
    except for the single ``round``: ``part / s`` is an exact f32
    division, and every ``q × s`` dequantize product is exactly
    representable (7 magnitude bits × a 1-bit significand).  Exactness
    is what makes the pipeline immune to XLA's fused-multiply-add
    contraction of ``mine + q*s`` — which skips the product's
    intermediate rounding and is applied or not depending on fusion
    context — so the traced Mode A program and the eager Mode B oracle
    (constants.reduce_q8_hop) are bit-identical BY CONSTRUCTION, not by
    codegen coincidence.  It also roundtrips integer-valued blocks
    (ones gradients, small-int test payloads) exactly.  The cost: the
    quantization step is ``amax``-rounded-up-to-a-power-of-two / 127 —
    between 1x and 2x the classic absmax step (~1.4x on average), well
    inside every shipped error bound.

    Computed with exact bit ops (exponent extraction + one doubling
    test), never an inexact ``log2``."""
    a = jnp.asarray(amax, jnp.float32)
    bits = jax.lax.bitcast_convert_type(a, jnp.uint32) \
        & jnp.uint32(0x7F800000)
    # 2^floor(log2 a) for normal a (mantissa bits zeroed); 0 below.
    s0 = jax.lax.bitcast_convert_type(bits, jnp.float32)
    scale = s0 * jnp.float32(2.0 ** -6)
    scale = jnp.where(jnp.float32(127.0) * scale < a, scale * 2, scale)
    return jnp.maximum(scale, jnp.float32(2.0 ** -126))


def _requant(part, noise):
    """Fresh-block-scale requantization of the f32 partial ``part``
    ((rows, block)): power-of-two absmax scale per block
    (:func:`po2_scale`), round-to-nearest (or stochastic
    ``floor(v + u)`` when ``noise`` is given), clip to the symmetric
    int8 range.  THE op sequence both implementations share — and
    exactly :class:`~mpi4torch_tpu.compress.codecs.BlockQ8Codec`'s
    encode on block-shaped data, so the fused hop is bit-equal to
    decode → add → encode through the codec."""
    amax = jnp.max(jnp.abs(part), axis=1, keepdims=True)
    scale = po2_scale(amax)
    v = part / scale
    if noise is None:
        r = jnp.round(v)
    else:
        r = jnp.floor(v + noise)
    q = jnp.clip(r, -127, 127).astype(jnp.int8)
    return q, scale


def requant_blocks(part, noise=None):
    """Encode block-shaped f32 data ((nblocks, block)) with fresh
    per-block scales — the hop-0 form of the fused hop (nothing has
    arrived yet, so there is nothing to dequantize or accumulate).
    Bit-identical to ``BlockQ8Codec.encode`` on the same data.  Returns
    ``(q, scale)`` with ``scale`` shaped (nblocks,)."""
    q, scale = _requant(part, noise)
    return q, scale[:, 0]


def block_residual(x, q, scale):
    """Quantization residual of block-shaped data against its encode:
    ``x - decode(q, scale)`` with ``scale`` shaped (nblocks,) — what the
    error-feedback rounds transfer and the per-hop EF carry re-injects."""
    return x - q.astype(jnp.float32) * scale[:, None]


def _hop_jnp(q, scale, mine, noise=None, *, want_resid: bool = False):
    part = mine + q.astype(jnp.float32) * scale[:, None]
    q2, scale2 = _requant(part, noise)
    resid = None
    if want_resid:
        resid = part - q2.astype(jnp.float32) * scale2
    return q2, scale2[:, 0], resid


# Jitted forms of the hop op sequence, for callers OUTSIDE a trace (the
# eager fold oracle, constants.reduce_q8_hop).  Bitwise cross-mode
# parity demands the oracle's arithmetic compile exactly like the traced
# pipeline's: op-by-op eager execution rounds ``mine + q*scale`` twice,
# while XLA contracts it to one fused multiply-add inside a jit — a
# 1-2 ulp divergence that would break the Mode A/B contract.  Routing
# the oracle through these jits gives both sides the same codegen.
_hop_jnp_jit = jax.jit(_hop_jnp, static_argnames=("want_resid",))
_requant_blocks_jit = jax.jit(requant_blocks)
_block_residual_jit = jax.jit(block_residual)


def _hop_kernel(want_resid: bool, stochastic: bool):
    """Kernel body for one row tile; closure over the static flags so
    the traced signature matches the operand list pallas_call passes."""

    def kernel(*refs):
        if stochastic:
            q_ref, s_ref, m_ref, n_ref, rest = \
                refs[0], refs[1], refs[2], refs[3], refs[4:]
            noise = n_ref[:]
        else:
            q_ref, s_ref, m_ref, rest = refs[0], refs[1], refs[2], refs[3:]
            noise = None
        part = m_ref[:] + q_ref[:].astype(jnp.float32) * s_ref[:]
        q2, scale2 = _requant(part, noise)
        rest[0][:] = q2
        rest[1][:] = scale2
        if want_resid:
            rest[2][:] = part - q2.astype(jnp.float32) * scale2

    return kernel


def _hop_pallas(q, scale, mine, noise, want_resid: bool, interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu  # noqa: F401

    nb, block = q.shape
    # int8 wants a (32, 128)-tiled layout: pad the block-row axis so the
    # row tile divides it (padded rows dequantize to 0 + 0 and requant
    # to q=0 with the zero-block scale po2_scale clamps to, 2^-126 —
    # inert either way, then sliced off).
    rows = -(-nb // _ROW_TILE) * _ROW_TILE
    if rows != nb:
        pad = rows - nb
        q = jnp.concatenate([q, jnp.zeros((pad, block), jnp.int8)])
        scale = jnp.concatenate([scale, jnp.ones((pad,), jnp.float32)])
        mine = jnp.concatenate([mine, jnp.zeros((pad, block), jnp.float32)])
        if noise is not None:
            noise = jnp.concatenate(
                [noise, jnp.zeros((pad, block), jnp.float32)])

    grid = (rows // _ROW_TILE,)
    row_spec = pl.BlockSpec((_ROW_TILE, block), lambda i: (i, 0))
    col_spec = pl.BlockSpec((_ROW_TILE, 1), lambda i: (i, 0))
    in_specs = [row_spec, col_spec, row_spec]
    operands = [q, scale[:, None], mine]
    if noise is not None:
        in_specs.append(row_spec)
        operands.append(noise)
    out_shape = [jax.ShapeDtypeStruct((rows, block), jnp.int8),
                 jax.ShapeDtypeStruct((rows, 1), jnp.float32)]
    out_specs = [row_spec, col_spec]
    if want_resid:
        out_shape.append(jax.ShapeDtypeStruct((rows, block), jnp.float32))
        out_specs.append(row_spec)

    out = pl.pallas_call(
        _hop_kernel(want_resid, noise is not None),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(*operands)
    q2, scale2 = out[0][:nb], out[1][:nb, 0]
    resid = out[2][:nb] if want_resid else None
    return q2, scale2, resid


def hop_available(block: int) -> bool:
    """Whether the Pallas kernel can serve this block size (the lane
    axis must tile to 128; other sizes take the jnp fallback even under
    ``quant_hop_impl="pallas"``)."""
    return block % 128 == 0


def dequant_accum_requant(
        q, scale, mine, *, noise=None, want_resid: bool = False,
        impl: Optional[str] = None,
) -> Tuple[jax.Array, jax.Array, Optional[jax.Array]]:
    """One fused quantized ring hop on block-shaped data.

    ``q``/``scale`` — the arriving encoded partial ((nblocks, block)
    int8 + (nblocks,) f32 scales); ``mine`` — this rank's zero-padded
    f32 contribution in the same block shape; ``noise`` — uniform [0, 1)
    samples for stochastic rounding (None = round-to-nearest).  Returns
    ``(q', scale', resid)`` where ``resid`` (only when ``want_resid``)
    is the fresh quantization residual ``part - decode(q', scale')`` —
    what the error-feedback rounds transfer.

    ``impl`` overrides :func:`config.quant_hop_impl`.  Both
    implementations are bit-identical; ``"pallas"`` off-TPU runs the
    kernel interpreted (the equivalence-test surface)."""
    if impl is None:
        impl = _config.quant_hop_impl()
    use_kernel = (impl == "pallas"
                  or (impl == "auto" and _on_tpu()))
    if use_kernel and hop_available(q.shape[1]):
        return _hop_pallas(q, scale, mine, noise, want_resid,
                           interpret=not _on_tpu())
    return _hop_jnp(q, scale, mine, noise, want_resid=want_resid)
