"""Wire-compression codecs: block-scaled int8 and (stochastic) bfloat16.

A codec is a pure, shape-polymorphic pair of maps

    encode(x, key=None) -> (payload, meta)      # payload: dict of arrays
    decode(payload, meta) -> x_approx           # original shape & dtype

where ``payload`` holds the arrays that actually ride the wire (the
collectives in compress/spmd.py ship its leaves through
``ppermute``/``all_gather``; compress/eager.py ships it through the
rendezvous) and ``meta`` is static Python data (shape/dtype bookkeeping)
that never leaves the host.  Codecs are deterministic given their inputs
(plus the PRNG key for stochastic codecs), so every rank decoding the
same payload reconstructs bit-identical values — the property the
all-gather stage of the compressed collectives relies on.

Shipped codecs (EQuARX, arxiv 2506.17615, is the design reference for the
block-scaled int8 family; "The Big Send-off", arxiv 2504.18658, motivates
keeping the choice per-callsite tunable):

=============  =====================================  ============  ========
name           scheme                                 wire (f32 in)  rounds
=============  =====================================  ============  ========
``q8``         per-256-block absmax-scaled int8       ~3.94x less    1
``q8_ef``      q8 + one error-feedback round          ~1.97x less    2
``q8_ef_hop``  q8 with per-hop stochastic rounding    ~3.94x less    1
               + per-hop error feedback (the hop
               residual folds into this rank's next
               in-schedule contribution)
``bf16``       round-to-nearest bfloat16              2x less        1
``bf16r``      stochastic-rounded bfloat16 (keyed)    2x less        1
=============  =====================================  ============  ========

The registry is the extension point the ROADMAP's topology-aware
autotuning will plug into: register a codec object under a name and every
facade op accepts ``compression="<name>"``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

Payload = Dict[str, Any]
Meta = Tuple


def _default_key():
    return jax.random.PRNGKey(0)


@dataclasses.dataclass(frozen=True)
class Codec:
    """Base codec: identity behaviour hooks plus the registry contract.

    ``ef_rounds`` > 1 marks an error-feedback codec: the collectives run
    the base scheme, then compress-and-sum the local quantization
    residuals in a second round (in-call error feedback), which cancels
    the first-order quantization error of the sum.  ``stochastic`` codecs
    consume a PRNG key per encode; the collectives derive per-rank,
    per-hop keys so rounding noise is independent across contributions
    (correlated noise would bias the sum).

    ``algorithms`` declares which collective wire algorithms
    (:mod:`mpi4torch_tpu.tune`) the codec composes with.  The compressed
    pipeline re-quantizes the partial sum at each ring hop
    (compress/spmd.py); that per-hop structure generalizes to every
    schedule whose channels are rings — ``ring`` itself, ``bidir``'s two
    counter-rotating chains, and ``torus``'s two striped grid walks —
    but not to the butterfly/tree/hierarchical schedules.  The
    in-schedule (``hop_fused``) block-q8 family declares the full
    ring-shaped trio; the bf16 family stays ring-only (its pipeline is
    the generic encoded ring).  The tune selector restricts auto choice
    to the declared algorithms, and explicit mismatched requests raise
    at the facade (comm.Allreduce); the registry side of the same
    predicate is ``AlgorithmSpec.codec_capable`` (tune/registry.py) —
    both must agree before a codec rides a wire.

    ``schedule_keyed`` marks stochastic codecs whose rounding noise is a
    pure function of the collective schedule (salt × hop × rank — no
    call counters, no data fingerprints): their Mode A and Mode B
    executions consume identical noise bits, so the quantized fold
    oracle (:func:`mpi4torch_tpu.constants.reduce_q8_hop`) holds them to
    BIT-identical cross-mode parity like the deterministic codecs.
    ``bf16r`` is deliberately not schedule-keyed (Mode B advances a
    per-call counter for fresh noise across steps), so its parity
    contract is statistical, not bitwise.

    ``hop_fused``/``hop_ef`` describe the in-schedule hop: ``hop_fused``
    codecs encode block-shaped data with exactly the
    ``ops/quant_kernels.py`` requant op sequence, so the pipeline may
    run dequantize→accumulate→requantize as ONE fused kernel per hop
    (bit-identical to ``decode``→add→``encode`` through the codec — a
    subclass that overrides ``encode``/``decode`` must reset it);
    ``hop_ef`` additionally folds each hop's quantization residual into
    the same rank's next in-schedule contribution (per-hop error
    feedback at single-round wire cost).
    """

    name: str
    stochastic: bool = False
    ef_rounds: int = 1
    algorithms: Tuple[str, ...] = ("ring",)
    schedule_keyed: bool = False
    hop_fused: bool = False
    hop_ef: bool = False

    def base(self) -> "Codec":
        """The single-round codec used for each error-feedback round."""
        return self

    # -- subclass surface ---------------------------------------------------
    def encode(self, x, key=None) -> Tuple[Payload, Meta]:
        raise NotImplementedError

    def decode(self, payload: Payload, meta: Meta):
        raise NotImplementedError

    # -- shared helpers -----------------------------------------------------
    def roundtrip(self, x, key=None):
        """decode(encode(x)) — the local lossy approximation; its
        difference from ``x`` is the residual error-feedback rounds
        compensate."""
        payload, meta = self.encode(x, key)
        return self.decode(payload, meta)

    def wire_bytes(self, shape, dtype) -> int:
        """Bytes a tensor of ``shape``/``dtype`` occupies on the wire once
        encoded (the sum of the payload leaves' sizes) — the
        bytes-on-wire accounting, computed from real encoded buffers so
        the number cannot drift from the implementation."""
        x = jnp.zeros(shape, dtype)
        payload, _ = self.encode(x, _default_key() if self.stochastic
                                 else None)
        return int(sum(leaf.size * leaf.dtype.itemsize
                       for leaf in jax.tree_util.tree_leaves(payload)))

    def _meta(self, x) -> Tuple[Tuple[int, ...], str]:
        xa = jnp.asarray(x)
        return tuple(xa.shape), str(xa.dtype)


@dataclasses.dataclass(frozen=True)
class BlockQ8Codec(Codec):
    """Block-scaled int8: each 256-element block of the flattened tensor
    is scaled and rounded to int8 (EQuARX's block-scaled quantization,
    arxiv 2506.17615 §3), with the scale a POWER OF TWO — block floating
    point (``ops/quant_kernels.po2_scale``): the smallest ``2^k`` with
    ``127·2^k ≥ absmax``.  Exact-by-construction arithmetic (the
    division and every dequantize product round nowhere) is what lets
    the in-schedule pipeline hold bitwise Mode A/B parity under any XLA
    fusion, and integer-valued blocks (ones gradients) roundtrip
    exactly.  Per-element error is bounded by half the power-of-two
    step — at most one int8 step of the block's absmax.  The f32 scale
    adds 4 bytes per block, so the wire ratio is 4 / (1 + 4/256) ≈
    3.94x for f32."""

    name: str = "q8"
    algorithms: Tuple[str, ...] = ("ring", "bidir", "torus")
    hop_fused: bool = True
    block: int = 256

    def _blocks(self, x):
        """Flatten + zero-pad ``x`` to (nblocks, block) f32 — the block
        layout shared with the in-schedule pipeline's ``chunk_blocks``
        (zero pad is inert under the power-of-two absmax scale)."""
        flat = jnp.asarray(x, jnp.float32).reshape(-1)
        total = max(flat.size, 1)
        nb = -(-total // self.block)
        pad = nb * self.block - flat.size
        if pad:
            flat = jnp.concatenate([flat, jnp.zeros((pad,), jnp.float32)])
        return flat.reshape(nb, self.block)

    def encode(self, x, key=None):
        # requant_blocks IS this codec's encode on block-shaped data
        # (ops/quant_kernels: po2_scale block-floating-point scales,
        # exact products/division) — one op sequence for the standalone
        # encode and the fused hop's requant, so the hop_fused
        # bit-equality contract cannot drift.
        from ..ops.quant_kernels import requant_blocks

        shape, dtype = self._meta(x)
        q, scale = requant_blocks(self._blocks(x))
        return {"q": q, "scale": scale}, ("q8", shape, dtype)

    def decode(self, payload, meta):
        _, shape, dtype = meta
        blocks = payload["q"].astype(jnp.float32) \
            * payload["scale"][:, None].astype(jnp.float32)
        total = math.prod(shape)
        return blocks.reshape(-1)[:total].reshape(shape).astype(dtype)


@dataclasses.dataclass(frozen=True)
class HopEFQ8Codec(BlockQ8Codec):
    """``q8`` with per-hop stochastic rounding and per-hop error
    feedback, at single-round (~3.94x) wire cost.

    Two changes relative to :class:`BlockQ8Codec`, both living inside
    the in-schedule pipeline (compress/spmd.py):

    * every requantization rounds stochastically — ``floor(v + u)``
      with ``u ~ U[0, 1)`` drawn from the *schedule* key (salt × hop ×
      rank; the noise enters ``ops/quant_kernels.py`` as an operand, so
      the Pallas kernel and the jnp fallback consume identical bits) —
      making each hop's requant unbiased, so quantization error
      accumulates as zero-mean noise instead of a systematic floor;
    * each hop's residual ``part - decode(requant(part))`` is carried on
      the encoding rank and folded into its NEXT in-schedule
      contribution (a different chunk of the same tensor — the EF-SGD
      move applied across hops instead of steps), so apart from each
      rank's final-hop residual nothing is lost to quantization within
      the call.

    The cross-chunk reinjection preserves the tensor's total mass to
    first order while the stochastic hops keep the per-element leakage
    zero-mean; for gradient traffic this recovers ``q8_ef``-grade
    convergence (regression-tested on the smoke transformer) without
    ``q8_ef``'s second wire round.  ``schedule_keyed`` means Mode A and
    Mode B reproduce the exact same noise, so cross-mode parity is
    bitwise like the deterministic codecs.  Outside a ring-shaped
    schedule (the standalone ``encode``, the compressed Allgather legs)
    it behaves as stochastically-rounded q8."""

    name: str = "q8_ef_hop"
    stochastic: bool = True
    schedule_keyed: bool = True
    hop_ef: bool = True

    def encode(self, x, key=None):
        from ..ops.quant_kernels import hop_noise, requant_blocks

        shape, dtype = self._meta(x)
        if key is None:
            key = _default_key()
        blocks = self._blocks(x)
        noise = hop_noise(key, blocks.shape[0], self.block)
        q, scale = requant_blocks(blocks, noise)
        return {"q": q, "scale": scale}, ("q8", shape, dtype)


@dataclasses.dataclass(frozen=True)
class BF16Codec(Codec):
    """Round-to-nearest bfloat16: exact halving of f32 wire bytes with
    ~2^-9 relative error; deterministic and key-free."""

    name: str = "bf16"

    def encode(self, x, key=None):
        shape, dtype = self._meta(x)
        q = jnp.asarray(x, jnp.float32).astype(jnp.bfloat16).reshape(-1)
        return {"q": q}, ("bf16", shape, dtype)

    def decode(self, payload, meta):
        _, shape, dtype = meta
        return payload["q"].astype(jnp.float32) \
            .reshape(shape).astype(dtype)


@dataclasses.dataclass(frozen=True)
class BF16StochasticCodec(Codec):
    """Stochastic-rounded bfloat16: adds uniform 16-bit noise to the f32
    mantissa before truncating to the high 16 bits, so rounding is
    unbiased (E[decode(encode(x))] = x) — the property that keeps
    many-step gradient accumulation drift-free where round-to-nearest
    introduces a systematic floor.  Keyed: the collectives fold rank and
    hop indices into the key so per-contribution noise is independent."""

    name: str = "bf16r"
    stochastic: bool = True

    def encode(self, x, key=None):
        shape, dtype = self._meta(x)
        if key is None:
            key = _default_key()
        x32 = jnp.asarray(x, jnp.float32).reshape(-1)
        bits = jax.lax.bitcast_convert_type(x32, jnp.uint32)
        noise = jax.random.bits(key, x32.shape, jnp.uint32) \
            & jnp.uint32(0xFFFF)
        hi = ((bits + noise) >> 16).astype(jnp.uint16)
        q = jax.lax.bitcast_convert_type(hi, jnp.bfloat16)
        return {"q": q}, ("bf16r", shape, dtype)

    def decode(self, payload, meta):
        _, shape, dtype = meta
        return payload["q"].astype(jnp.float32) \
            .reshape(shape).astype(dtype)


@dataclasses.dataclass(frozen=True)
class ErrorFeedbackCodec(Codec):
    """A base codec run with one in-call error-feedback round: the
    collective transfers ``base(x)`` and then ``base(x - decode(base(x)))``
    and sums both, cancelling each rank's first-order quantization error
    (EF-SGD, Karimireddy et al. 2019, folded into the collective).  Wire
    cost is 2x the base codec — for ``q8_ef`` still ~2x under fp32 — and
    accuracy improves by roughly another factor of 127."""

    name: str = "q8_ef"
    ef_rounds: int = 2
    # The residual round tracks per-hop residuals at the rows of the
    # chunks this rank encoded — a property of the ring walk itself, so
    # it holds on every ring-shaped channel (ring, bidir's two chains,
    # torus's two grid walks) and the residual round rides the same
    # channel as the values it corrects.
    algorithms: Tuple[str, ...] = ("ring", "bidir", "torus")
    _base: Codec = dataclasses.field(default_factory=BlockQ8Codec)

    def base(self) -> Codec:
        return self._base

    def encode(self, x, key=None):
        return self._base.encode(x, key)

    def decode(self, payload, meta):
        return self._base.decode(payload, meta)

    def wire_bytes(self, shape, dtype) -> int:
        return self.ef_rounds * self._base.wire_bytes(shape, dtype)


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_REGISTRY: Dict[str, Codec] = {}


def register_codec(codec: Codec) -> Codec:
    """Register ``codec`` under ``codec.name`` (later topology-aware
    autotuners select among registered codecs per callsite).  Returns the
    codec so registration can wrap construction."""
    if not codec.name:
        raise ValueError("codec must have a non-empty name")
    _REGISTRY[codec.name] = codec
    return codec


def available_codecs() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def get_codec(spec) -> Optional[Codec]:
    """Resolve a ``compression=`` argument to a codec object.

    ``None``/``False``/``"none"`` mean no compression; a string looks up
    the registry; a :class:`Codec` instance passes through — ad-hoc
    codecs need no *registration*, but they must subclass :class:`Codec`
    (the pipeline relies on its full contract: ``name`` for spans and
    rendezvous signatures, ``ef_rounds``/``base()`` for the
    error-feedback rounds), so a bare encode/decode object is rejected
    here rather than crashing mid-collective."""
    if spec is None or spec is False:
        return None
    if isinstance(spec, str):
        if spec in ("none", "off"):
            return None
        codec = _REGISTRY.get(spec)
        if codec is None:
            raise ValueError(
                f"unknown compression codec {spec!r}; available: "
                f"{', '.join(available_codecs())}")
        return codec
    if isinstance(spec, Codec):
        return spec
    raise TypeError(
        f"compression must be a registered codec name, a Codec subclass "
        f"instance, or None; got {spec!r}")


register_codec(BlockQ8Codec())
register_codec(HopEFQ8Codec())
register_codec(BF16Codec())
register_codec(BF16StochasticCodec())
register_codec(ErrorFeedbackCodec())
