"""Flagship model: decoder-only transformer, distributed 2D (dp x sp).

The capstone composition of the framework's strategy layer (SURVEY.md
§2.5): data parallelism over one mesh axis via the reference's two-Allreduce
recipe, and long-context sequence/context parallelism over a second axis —
the sequence dimension is sharded across ranks and attention runs as ring
attention (blockwise, K/V circulating over the differentiable
Isend/Irecv ring) or Ulysses (head<->sequence Alltoall).  Every distributed
movement is an ``MPI_Communicator`` op, so the same model runs on the eager
thread-SPMD runtime, inside ``run_spmd``, or in a user-managed 2D
``shard_map`` via ``comm_from_mesh`` (the intended TPU deployment).

A configuration may state its stack as data (``TransformerConfig.layers``,
one :class:`LayerSpec` a layer): Kimi Delta Attention, latent attention,
a Mamba-2 state-space mixer or grouped-query attention with a window, a
rotation, normed queries and keys and an output gate of its own as the
mixer, the held share of a top-k expert layer as the FFN, or a layer of
one of the two parts alone (doc/layer_spec.md).  KDA runs on the
training path only and Mamba-2 on the serving path only; latent
attention, the attention mixer and the expert share run on both
(serve/kv.py).

TPU-first shapes: all compute is batched matmul/einsum (MXU), parameters
and activations stay in the caller's dtype (bfloat16-ready), and the
sequence axis per rank is static so XLA tiles cleanly.
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..constants import MPI_SUM
from ..ops.flash import RESIDUAL_NAMES as _FLASH_RESIDUALS, \
    flash_attention, flash_block_attention, merge_partials
from ..ops.kda import kda_chunked
from ..ops.ssd import CHUNK as _SSD_CHUNK, ssd_chunked, ssd_step
from ..ops.paged_attention import index_scores
from ..parallel.attention import ring_attention, \
    ulysses_attention, zigzag_ring_attention
from ..parallel.dp import all_average_tree, replicated_tree
from ..parallel.moe import Experts, experts_ffn, \
    held_experts_ffn, init_experts, route_experts, \
    init_moe, moe_ffn, moe_ffn_dense
from ..parallel.zero import zero3_step, zero_step
from ..parallel.ring import ring_shift
from ..runtime import CommError
from ..utils.profiling import layer_scope


# What a rematerialised mixer keeps of its forward pass.
_SAVED_IN_REMAT = jax.checkpoint_policies.save_only_these_names("kda_out")
# What a rematerialised uniform block keeps where the chip has the room
# (:func:`_remat_kept_layers`): the outputs of the products its backward
# reads again, ``y @ wqkv``, ``x + o @ wo`` and ``y @ w1``, beside the
# flash kernel's ``(out, lse)``.  ``h @ w2`` is not among them: nothing
# in the block reads it again.
_KEPT_PRODUCTS = ("qkv", "attn_residual", "ffn_in")
# The share of the device's memory that three times the parameters and
# the kept outputs may fill together.  The quarter left free is what a
# step holds and the rule does not count, as the TPU's compiler sized it
# for a described v5e at Mistral-7B's widths, 8,192 tokens a chip
# (memory_analysis(), PR 46): 1.75 GB of temporaries in a step that
# keeps nothing (the logits and their gradient, one block's recomputed
# forward) and a data-parallel step's averaged copy of the parameters,
# 2.27 GB: 4.0 GB of the chip's 16.9, 24%.  (Since PR 51 no step of this
# file makes that copy, :func:`train_step`; whether the rule should then
# keep more is ROADMAP C4's to measure.)
_REMAT_ROOM = 0.75


@dataclass(frozen=True)
class KDA:
    """Mixer: Kimi Delta Attention (ops/kda.py).  ``n_heads`` heads of
    ``head_dim`` for keys and values alike, a causal depthwise
    convolution of ``conv`` taps on q, k and v, decay and output gate
    each through a low-rank pair of width ``head_dim``."""
    n_heads: int
    head_dim: int
    conv: int = 4


@dataclass(frozen=True)
class Mamba2:
    """Mixer: a Mamba-2 state-space layer (ops/ssd.py).  ``n_heads``
    heads of ``head_dim`` channels (``d_inner = n_heads * head_dim``),
    each with a state of ``head_dim x d_state`` under one scalar decay;
    ``n_groups`` groups of heads share their ``B`` and ``C``; a causal
    depthwise convolution of ``conv`` taps with a bias on ``[x | B |
    C]``; a sigmoid-linear gate and an rmsnorm over each group's
    ``d_inner / n_groups`` channels before the output projection.
    Between tokens a sequence keeps the state and the convolution's last
    ``conv - 1`` inputs and nothing else.  Served only: the training
    forward refuses the mixer by name (the scan's backward is not
    written)."""
    n_heads: int
    head_dim: int
    d_state: int
    n_groups: int
    conv: int = 4
    chunk: int = _SSD_CHUNK

    def __post_init__(self):
        if self.n_groups < 1 or self.n_heads % self.n_groups:
            raise ValueError(
                f"n_heads={self.n_heads} must be a positive multiple of "
                f"n_groups={self.n_groups}")
        if self.conv < 2:
            raise ValueError(f"Mamba2.conv={self.conv} must be >= 2")

    @property
    def d_inner(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def conv_dim(self) -> int:
        """Channels the convolution runs over: ``[x | B | C]``."""
        return self.d_inner + 2 * self.n_groups * self.d_state


@dataclass(frozen=True)
class Indexer:
    """The learned selection of sparse latent attention: ``n_heads``
    index queries of ``head_dim`` channels a token, up from the layer's
    normed query latent, against ONE index key a cached token; the first
    ``rope`` channels of both are rotated by position.  A cached
    position's score is ``sum_j w_j relu(q_j . k)`` with a learned weight
    a head and token, and a query attends the ``top_k`` positions of
    the largest scores (all of them while there are no more)."""
    n_heads: int
    head_dim: int
    rope: int
    top_k: int

    def __post_init__(self):
        if self.rope % 2 or not 0 <= self.rope <= self.head_dim:
            raise ValueError(
                f"rope pairs channels: Indexer.rope={self.rope} must be "
                f"even and at most head_dim={self.head_dim}")
        if self.top_k < 1:
            raise ValueError(f"Indexer.top_k={self.top_k} must be >= 1")


@dataclass(frozen=True)
class MLA:
    """Mixer: multi-head latent attention.  Keys and values come up from
    one latent of ``kv_rank``; a key is ``qk_nope`` channels of its own
    head plus ``qk_rope`` channels shared by all heads; a value has
    ``v_dim`` channels.  ``q_rank > 0`` brings the query up from a normed
    latent of that rank as well (0: one projection).  ``rope`` rotates
    the ``qk_rope`` channels of every query head and of the shared key
    by their position (``TransformerConfig.rope_theta``); without it no
    channel knows a position.  ``q_scale`` and ``kv_scale`` multiply the
    normed query latent and the normed key-value latent (a model that
    scales them by ``sqrt(d_model / rank)``); the scaled key-value
    latent is what a serving cache row holds.

    ``index`` makes the attention sparse: an :class:`Indexer` on a layer
    that scores every cached position and attends the ``top_k`` it
    selects, ``"shared"`` on a layer that attends the selection of the
    nearest scoring layer below it and has no indexer of its own
    (:class:`TransformerConfig` refuses one with no such layer).  Served
    only: the training forward refuses an indexed mixer by name."""
    n_heads: int
    kv_rank: int
    qk_nope: int
    qk_rope: int
    v_dim: int
    q_rank: int = 0
    rope: bool = False
    q_scale: float = 1.0
    kv_scale: float = 1.0
    index: Union[None, Indexer, str] = None

    def __post_init__(self):
        if self.rope and self.qk_rope % 2:
            raise ValueError(
                f"rope pairs channels: qk_rope={self.qk_rope} must be even")
        if self.q_scale != 1.0 and not self.q_rank:
            raise ValueError(
                "q_scale scales the normed query latent: it needs q_rank > 0")
        if not (self.index is None or self.index == "shared"
                or isinstance(self.index, Indexer)):
            raise ValueError(
                f"MLA.index is None, an Indexer or \"shared\", got "
                f"{self.index!r}")
        if isinstance(self.index, Indexer) and not self.q_rank:
            raise ValueError(
                "an Indexer's queries come up from the normed query latent: "
                "it needs q_rank > 0")

    @property
    def scores(self) -> bool:
        """Whether the layer has an indexer of its own."""
        return isinstance(self.index, Indexer)


@dataclass(frozen=True)
class GQA:
    """Mixer: grouped-query softmax attention stated on the layer, for a
    stack whose attention layers differ.  ``n_heads`` query heads and
    ``n_kv_heads`` key-value heads of ``head_dim`` channels (query head
    ``h`` reads KV head ``h // (n_heads // n_kv_heads)``; ``n_heads *
    head_dim`` need not be the stream's width).  ``window > 0``: a
    position reads itself and the ``window - 1`` before it, and on the
    serving path the layer's pages lie in the WINDOW class, which holds
    the pages the window touches and frees the rest (serve/paging.py);
    ``0``: every position at or before it.  ``rope`` rotates every
    channel of the queries and keys by position at ``rope_theta``;
    without it the layer has no position signal of its own.
    ``qk_norm``: an rmsnorm with a learned scale over each query head's
    and each key head's channels, before the rotation (leaves
    ``q_norm``, ``k_norm``).  ``gate``: the attention's output times
    ``sigmoid`` of a projection of the layer's normed input, head by
    head and channel by channel, before the output projection (the last
    ``n_heads * head_dim`` columns of the fused ``wqkv``)."""
    n_heads: int
    n_kv_heads: int
    head_dim: int
    window: int = 0
    rope: bool = False
    rope_theta: float = 10000.0
    qk_norm: bool = False
    gate: bool = False

    def __post_init__(self):
        if self.n_kv_heads < 1 or self.n_heads % self.n_kv_heads:
            raise ValueError(
                f"n_heads={self.n_heads} must be a positive multiple of "
                f"n_kv_heads={self.n_kv_heads}")
        if self.window < 0:
            raise ValueError(
                f"GQA.window must be >= 0 (0 = every position before), "
                f"got {self.window}")
        if self.rope and self.head_dim % 2:
            raise ValueError(
                f"rope pairs channels: head_dim={self.head_dim} must be "
                "even")


@dataclass(frozen=True)
class LayerSpec:
    """One layer of a stack whose layers differ.  ``mixer``: ``None`` is
    the configuration's own attention (``n_heads``, ``n_kv_heads``,
    ``rope``, ``attn_window``), else a :class:`KDA`, an :class:`MLA`, a
    :class:`Mamba2` or a :class:`GQA` (attention with head counts, a
    window and a rotation of the LAYER's own, which may stand beside an
    expert FFN and post-norms).
    ``ffn``: ``None`` is the configuration's dense FFN (``ffn``,
    ``d_ff``), else the :class:`~mpi4torch_tpu.parallel.moe.Experts`
    share this rank holds.  ``post_norm`` puts a second norm on each
    branch, after the mixer and after the FFN and before the residual
    sum (a "sandwich"): leaves ``ln1_post`` and ``ln2_post``.

    ``branch`` is a shortcut: the :class:`~mpi4torch_tpu.parallel.moe.
    Experts` share computed on THIS layer's ``ln2`` rows (leaves
    ``branch``), beside the layer's own FFN, and carried along the stack
    until a layer with ``join`` adds it to the stream after its own FFN
    residual: the same layer, or a later one, so that the branch runs
    beside every mixer and FFN in between.  One branch is open at a
    time, and every branch is joined once (:class:`TransformerConfig`
    refuses anything else).

    ``only`` makes the layer ONE part: ``"mixer"`` is ``x + Mixer(N(x))``
    and nothing else (leaves ``ln1`` and the mixer's; no ``ln2``, no
    FFN), ``"ffn"`` is ``x + FFN(N(x))`` (leaves ``ln2`` and the FFN's;
    no ``ln1``, no mixer: ``mixer`` stays ``None`` and names nothing).
    One norm and one residual sum a layer; no second norm and no
    shortcut.

    ``route_on`` says which rows the expert FFN's router reads: ``""``,
    the rows the experts read (``ln2``'s output, behind the mixer);
    ``"input"``, the layer's input as it enters, before the first norm
    and the mixer (a router placed before attention: the choice of
    experts is known while the mixer runs).  The experts still read
    ``ln2``'s output.  Training path only."""
    mixer: Union[None, KDA, MLA, Mamba2, GQA] = None
    ffn: Optional[Experts] = None
    post_norm: bool = False
    branch: Optional[Experts] = None
    join: bool = False
    only: str = ""
    route_on: str = ""

    @property
    def shortcut(self) -> bool:
        """Whether the layer carries or joins a branch."""
        return self.branch is not None or self.join


@dataclass(frozen=True)
class TransformerConfig:
    """Static model hyperparameters (kept OUT of the parameter pytree so
    grads/optimizer tree-maps see arrays only).

    ``n_experts > 0`` switches every block's FFN to an expert-parallel MoE
    (capacity-based top-1 routing over the differentiable ``Alltoall``,
    parallel/moe.py); ``capacity`` is the per-(expert, source-rank) slot
    count, ``aux_coef`` weights the load-balancing loss in :func:`lm_loss`.

    ``remat`` makes each block a ``jax.checkpoint`` region: the backward
    pass keeps the block's input and runs its forward again, so
    activation memory drops from O(layers) to O(1) blocks — the
    HBM-for-FLOPs trade.  A uniform block (the configuration's own
    attention and dense FFN) keeps, by name, the outputs of the products
    its backward reads again — ``y @ wqkv``, ``x + o @ wo`` and
    ``y @ w1`` — and the flash kernel's ``(out, lse)``:
    ``(n_heads + 2 kv_heads) head_dim + 2 d_model + 2 d_ff`` values of
    the parameters' dtype a token a layer (``d_ff`` under gelu) and a
    float32 a head, 86,144 bytes at Mistral-7B's widths in bfloat16.
    How many layers keep them is read at trace time from the local
    token count, the parameters' bytes and the memory the first local
    device reports (:func:`_remat_kept_layers`): the first layers, as
    many as fit; every layer where the backend reports no limit (the
    CPU); the layers past the count keep nothing.  Still recomputed in
    every layer: the norms, the rotation, ``silu(gate) * up`` or the
    gelu, the residual sums and the layout changes; the top-1
    ``Alltoall`` expert FFN (``n_experts > 0``) with its collectives;
    and, under sequence parallelism, the attention (ring, zigzag and
    ulysses keep no pair: every ring step's block would keep its own).
    ``h @ w2`` is neither kept nor run again: nothing reads it twice.
    The regions of a per-layer spec (one a mixer, one an FFN) keep
    ``kda_out`` and nothing else.  Collectives inside a rematted block
    re-execute during backward, which is SPMD-symmetric (every rank
    reruns the same sequence, so no deadlock); it requires the traced
    (SPMD/jit) path — the eager thread-SPMD backend's ops execute
    imperatively and refuse tracing.

    ``nope`` gives the configuration's own attention no position signal
    at all: no rotation (``rope`` must be off) and no learned table (no
    ``pos`` leaf), for a stack whose other layers carry the order (the
    attention layers of a state-space hybrid).

    ``embed_scale`` multiplies the embedding rows a token looks up
    (:func:`embed_tokens`): ``sqrt(d_model)`` for a model trained with
    unit-size embeddings under a width-independent parametrisation;
    ``1.0`` leaves the rows as they are.

    ``norm_eps`` is what the stream's norms (``ln1``, ``ln2``, the
    post-norms, ``ln_f``) add under their root, rmsnorm and layernorm
    alike; a mixer's inner norms (QK norms, a latent's, a head norm)
    keep ``1e-5``."""
    vocab: int
    d_model: int
    n_heads: int
    n_layers: int
    d_ff: int
    max_seq: int
    n_kv_heads: int = 0
    attn_window: int = 0
    rope: bool = False
    rope_theta: float = 10000.0
    nope: bool = False
    norm: str = "layernorm"
    ffn: str = "gelu"
    n_experts: int = 0
    capacity: int = 0
    aux_coef: float = 0.01
    remat: bool = False
    layers: Tuple[LayerSpec, ...] = ()
    embed_scale: float = 1.0
    norm_eps: float = 1e-5

    def __post_init__(self):
        if self.layers:
            # The stack stated as data: one LayerSpec a layer.  Without
            # one every layer is LayerSpec() and nothing changes.
            if len(self.layers) != self.n_layers:
                raise ValueError(
                    f"layers has {len(self.layers)} entries for n_layers="
                    f"{self.n_layers}")
            if self.n_experts > 0:
                raise ValueError(
                    "a layer spec names its expert layers itself "
                    "(LayerSpec.ffn); n_experts is the uniform top-1 MoE")
            for i, s in enumerate(self.layers):
                if s.only not in ("", "mixer", "ffn"):
                    raise ValueError(
                        f"layer {i}: LayerSpec.only is \"\", \"mixer\" or "
                        f"\"ffn\", got {s.only!r}")
                if s.only and (s.post_norm or s.shortcut or (
                        s.ffn if s.only == "mixer" else s.mixer) is not None):
                    raise ValueError(
                        f"layer {i} is its {s.only} alone (LayerSpec.only): "
                        "it has one norm and one residual sum, and names no "
                        "other part, second norm or shortcut")
            for i, s in enumerate(self.layers):
                if s.route_on not in ("", "input"):
                    raise ValueError(
                        f"layer {i}: LayerSpec.route_on is \"\" or "
                        f"\"input\", got {s.route_on!r}")
                if s.route_on and (s.ffn is None or s.only or s.shortcut):
                    raise ValueError(
                        f"layer {i}: route_on={s.route_on!r} places the "
                        "router of the layer's own expert FFN (LayerSpec."
                        "ffn) before its mixer: it needs both parts and no "
                        "shortcut")
            if any(s.mixer is None and not s.only
                   and (s.ffn is not None or s.post_norm or s.shortcut)
                   for s in self.layers):
                raise ValueError(
                    "an expert FFN, a post-norm or a shortcut branch needs "
                    "a KDA or MLA mixer, or attention stated on the layer "
                    "(GQA): the configuration's own attention block "
                    "carries its own FFN and norms")
            scoring = None
            for i, s in enumerate(self.layers):
                index = getattr(s.mixer, "index", None)
                if isinstance(index, Indexer):
                    scoring = i
                elif index == "shared" and scoring is None:
                    raise ValueError(
                        f"layer {i} shares a selection (MLA.index="
                        "\"shared\") and no layer below it has an Indexer "
                        "to make one")
            open_at = None
            for i, s in enumerate(self.layers):
                if s.branch is not None:
                    if open_at is not None:
                        raise ValueError(
                            f"layer {i} opens a shortcut branch while layer "
                            f"{open_at}'s is not joined yet")
                    open_at = i
                if s.join:
                    if open_at is None:
                        raise ValueError(
                            f"layer {i} joins a shortcut branch, and none "
                            "is open: a branch is joined once")
                    open_at = None
            if open_at is not None:
                raise ValueError(
                    f"layer {open_at}'s shortcut branch is never joined")
        if self.n_experts > 0 and self.capacity <= 0:
            # capacity=0 would silently capacity-drop every token — the
            # model would train with no FFN path at all.
            raise ValueError(
                f"n_experts={self.n_experts} requires capacity > 0, got "
                f"{self.capacity}")
        if self.n_kv_heads:
            # Grouped-query attention (ops/flash.py): q head h reads KV
            # head h // (n_heads // n_kv_heads).  0 = plain MHA.
            if self.n_kv_heads < 0 or self.n_heads % self.n_kv_heads != 0:
                raise ValueError(
                    f"n_heads={self.n_heads} must be a positive multiple "
                    f"of n_kv_heads={self.n_kv_heads}")

        if self.attn_window < 0:
            raise ValueError(
                f"attn_window must be >= 0 (0 = full causal attention), "
                f"got {self.attn_window}")
        if self.nope and self.rope:
            raise ValueError(
                "nope is attention with no position signal; rope rotates "
                "by position")
        if self.rope and (self.d_model // self.n_heads) % 2 != 0:
            raise ValueError(
                f"rope requires an even head_dim, got "
                f"{self.d_model // self.n_heads}")
        if self.norm not in ("layernorm", "rmsnorm"):
            raise ValueError(f"unknown norm {self.norm!r}")
        if self.ffn not in ("gelu", "swiglu"):
            raise ValueError(f"unknown ffn {self.ffn!r}")
        if self.ffn == "swiglu" and self.n_experts > 0:
            raise ValueError(
                "ffn='swiglu' applies to the dense FFN; the MoE experts "
                "(n_experts > 0) keep their own gelu expert MLPs")

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    @property
    def pos_table(self) -> bool:
        """Whether the model adds a learned position table (``pos``) to
        its embedding: under neither ``rope`` nor ``nope``."""
        return not (self.rope or self.nope)

    @property
    def layer_specs(self) -> Tuple[LayerSpec, ...]:
        return self.layers or (LayerSpec(),) * self.n_layers


def init_transformer(key, cfg: TransformerConfig,
                     dtype=jnp.float32) -> Dict[str, Any]:
    """Parameter pytree for a pre-LN decoder-only transformer."""
    vocab, d_model, d_ff = cfg.vocab, cfg.d_model, cfg.d_ff
    n_layers, max_seq = cfg.n_layers, cfg.max_seq
    def dense(key, m, n):
        return jax.random.normal(key, (m, n), dtype) / jnp.sqrt(
            jnp.asarray(m, dtype))

    def norm_p():
        p = {"scale": jnp.ones((d_model,), dtype)}
        if cfg.norm == "layernorm":
            p["bias"] = jnp.zeros((d_model,), dtype)
        return p

    keys = iter(jax.random.split(key, 4 + 7 * n_layers))
    params: Dict[str, Any] = {
        "embed": jax.random.normal(next(keys), (vocab, d_model), dtype) * 0.02,
        "blocks": [],
    }
    # The pos key is drawn UNCONDITIONALLY at its historical position in
    # the stream (and discarded under rope): making the draw conditional
    # would shift every later key and silently change all existing
    # non-rope initializations for the same seed.
    pos_key = next(keys)
    if cfg.pos_table:
        # Learned absolute positions; under rope the encoding is applied
        # rotationally to q/k instead (no table, no max_seq cap on the
        # encoding itself).
        params["pos"] = jax.random.normal(
            pos_key, (max_seq, d_model), dtype) * 0.02
    params["ln_f"] = norm_p()
    params["unembed"] = dense(next(keys), d_model, vocab)
    for spec in cfg.layer_specs:
        # Fused projection: h q-heads plus 2*h_kv KV heads (= 3*d_model
        # for plain MHA; smaller under GQA).
        hd = d_model // cfg.n_heads
        if spec.only == "ffn":
            blk = {}
        elif spec.mixer is None:
            blk = {
                "ln1": norm_p(),
                "wqkv": dense(next(keys), d_model,
                              d_model + 2 * cfg.kv_heads * hd),
                "wo": dense(next(keys), d_model, d_model),
            }
        else:
            blk = {"ln1": norm_p(),
                   "mixer": _init_mixer(next(keys), spec.mixer, d_model,
                                        dtype)}
            if spec.post_norm:
                blk["ln1_post"], blk["ln2_post"] = norm_p(), norm_p()
        if spec.only == "mixer":
            params["blocks"].append(blk)
            continue
        blk["ln2"] = norm_p()
        if spec.branch is not None:
            blk["branch"] = init_experts(next(keys), spec.branch, d_model,
                                         dtype)
        if spec.ffn is not None:
            blk["experts"] = init_experts(next(keys), spec.ffn, d_model,
                                          dtype)
        elif cfg.n_experts > 0:
            blk["moe"] = init_moe(next(keys), cfg.n_experts, d_model, d_ff,
                                  dtype)
        elif cfg.ffn == "swiglu":
            # Gate and up projections fused into one (d, 2*d_ff) matmul.
            blk["w1"] = dense(next(keys), d_model, 2 * d_ff)
            blk["w2"] = dense(next(keys), d_ff, d_model)
        else:
            blk["w1"] = dense(next(keys), d_model, d_ff)
            blk["w2"] = dense(next(keys), d_ff, d_model)
        params["blocks"].append(blk)
    return params


def _init_mixer(key, spec, d_model: int, dtype) -> Dict[str, Any]:
    """Leaves of a :class:`KDA`, :class:`MLA`, :class:`Mamba2` or
    :class:`GQA` mixer."""
    ks = iter(jax.random.split(key, 10))

    def dense(m, n):
        return jax.random.normal(next(ks), (m, n), dtype) / jnp.sqrt(
            jnp.asarray(m, dtype))

    if isinstance(spec, MLA):
        h = spec.n_heads
        q_in = spec.q_rank or d_model
        query = {"wq": dense(q_in, h * (spec.qk_nope + spec.qk_rope))}
        if spec.q_rank:
            query["wqa"] = dense(d_model, spec.q_rank)
            query["q_norm"] = {"scale": jnp.ones((spec.q_rank,), dtype)}
        out = {**query,
               "wa": dense(d_model, spec.kv_rank + spec.qk_rope),
               "kv_norm": {"scale": jnp.ones((spec.kv_rank,), dtype)},
               "wb": dense(spec.kv_rank, h * (spec.qk_nope + spec.v_dim)),
               "wo": dense(h * spec.v_dim, d_model)}
        if spec.scores:
            ix = spec.index
            out["index"] = {
                "wq": dense(spec.q_rank, ix.n_heads * ix.head_dim),
                "wk": dense(d_model, ix.head_dim),
                "k_norm": {"scale": jnp.ones((ix.head_dim,), dtype),
                           "bias": jnp.zeros((ix.head_dim,), dtype)},
                "ww": dense(d_model, ix.n_heads)}
        return out
    if isinstance(spec, GQA):
        h, h_kv, hd = spec.n_heads, spec.n_kv_heads, spec.head_dim
        # One fused projection: [q | k | v], and the gate behind them.
        out = {"wqkv": dense(d_model, (h * (1 + spec.gate) + 2 * h_kv) * hd),
               "wo": dense(h * hd, d_model)}
        if spec.qk_norm:
            out["q_norm"] = {"scale": jnp.ones((hd,), dtype)}
            out["k_norm"] = {"scale": jnp.ones((hd,), dtype)}
        return out
    if isinstance(spec, Mamba2):
        h = spec.n_heads
        # As the state-space models start them: exp(a_log) in [1, 16],
        # softplus(dt_bias) in [1e-3, 1e-1], D ones.
        dt = jnp.exp(jax.random.uniform(next(ks), (h,), jnp.float32,
                                        jnp.log(1e-3), jnp.log(1e-1)))
        return {"in_proj": dense(d_model, spec.d_inner + spec.conv_dim + h),
                "conv": dense(spec.conv, spec.conv_dim),
                "conv_bias": jnp.zeros((spec.conv_dim,), dtype),
                "dt_bias": jnp.log(jnp.expm1(dt)).astype(dtype),
                "a_log": jnp.log(jax.random.uniform(
                    next(ks), (h,), jnp.float32, 1.0, 16.0)).astype(dtype),
                "d": jnp.ones((h,), dtype),
                "norm": {"scale": jnp.ones((spec.d_inner,), dtype)},
                "out_proj": dense(spec.d_inner, d_model)}
    h, hd = spec.n_heads, spec.head_dim
    # Decay as the delta-rule models start it: exp(a_log) in [1, 16],
    # softplus(dt_bias) in [1e-3, 1e-1].
    dt = jnp.exp(jax.random.uniform(next(ks), (h * hd,), jnp.float32,
                                    jnp.log(1e-3), jnp.log(1e-1)))
    return {"wqkv": dense(d_model, 3 * h * hd),
            "conv": dense(spec.conv, 3 * h * hd),
            "wf1": dense(d_model, hd), "wf2": dense(hd, h * hd),
            "dt_bias": jnp.log(jnp.expm1(dt)).astype(dtype),
            "a_log": jnp.log(jax.random.uniform(
                next(ks), (h,), jnp.float32, 1.0, 16.0)).astype(dtype),
            "wg1": dense(d_model, hd), "wg2": dense(hd, h * hd),
            "wb": dense(d_model, h),
            "norm": {"scale": jnp.ones((hd,), dtype)},
            "wo": dense(h * hd, d_model)}


# What an expert layer counted (``parallel.moe.experts_ffn``) and the name
# ``_forward`` hands each count out under, a row an expert layer.
_COUNTED = {"rows": "moe_rows", "overflow": "moe_overflow_calls",
            "sent": "ep_rows_sent", "padding": "ep_padding_rows",
            "rounds": "ep_overflow_rounds"}


def _layer_norm(x, p, eps: float = 1e-5):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _rms_norm(x, p, eps: float = 1e-5):
    # No centering, no bias: normalize by the root-mean-square alone —
    # one fewer reduction and a smaller param set than LayerNorm.
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x / jnp.sqrt(ms + eps) * p["scale"]


def _norm(cfg: TransformerConfig, x, p):
    """A norm of the stream, under the configuration's ``norm_eps``."""
    norm = _rms_norm if cfg.norm == "rmsnorm" else _layer_norm
    return norm(x, p, cfg.norm_eps)


def _rope_rotate(cfg: TransformerConfig, x, positions):
    """Rotary position embedding (half-split convention): rotate each
    (x[i], x[i+hd/2]) pair of head-dim channels by ``pos * theta^(-2i/hd)``.
    Attention scores of two rotated vectors depend only on their position
    DIFFERENCE — the relative encoding that lets trained models attend
    beyond any absolute position table (the long-context default; the
    learned absolute table hard-caps at max_seq).  ``positions`` (s,) may
    be traced (rank-symbolic global offsets under SPMD), so the sharded
    shards of one sequence rotate consistently and ring/Ulysses need no
    special handling: q/k are rotated BEFORE any transport.

    ``positions`` may also be ``(b, s)`` — per-ROW positions, the
    continuous-batching decode path (:mod:`mpi4torch_tpu.serve`) where
    every slot of the batch sits at its own position.  The rotation is
    per head-dim channel, so tensor-parallel head sharding composes
    unchanged either way.  ``cfg`` is read for ``rope_theta`` alone: a
    :class:`GQA` mixer, which states its own, is passed in its place."""
    hd = x.shape[-1]
    half = hd // 2
    ct = _compute_dtype_rope(x)
    inv = cfg.rope_theta ** (-jnp.arange(half, dtype=ct) * 2.0 / hd)
    positions = jnp.asarray(positions)
    if positions.ndim == 1:
        ang = positions.astype(ct)[:, None] * inv[None, :]    # (s, half)
        cos = jnp.cos(ang)[None, :, None, :]
        sin = jnp.sin(ang)[None, :, None, :]
    else:
        ang = positions.astype(ct)[..., None] * inv           # (b, s, half)
        cos = jnp.cos(ang)[:, :, None, :]
        sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half].astype(ct), x[..., half:].astype(ct)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1).astype(x.dtype)


def _compute_dtype_rope(x):
    # Angles at least f32 (bf16 positions would alias long-context
    # phases); f64 params keep f64 so oracle tests compare at 1e-12.
    return jnp.promote_types(x.dtype, jnp.float32)


def _split_qkv(cfg: TransformerConfig, blk, y, positions=None,
               size: int = 1):
    """Project ``y`` (b, s, d) through the fused qkv matrix and split into
    ``q (b, s, h, hd)`` and ``k``/``v (b, s, kv_heads, hd)`` — the ONE
    place the asymmetric GQA projection layout lives (forward, prefill,
    decode and the serving walker all slice through here, so they cannot
    drift apart).  ``size`` is the tensor-parallel world a serving shard
    ``[q_r | k_r | v_r]`` was cut for: the head counts are then this
    rank's, ``n_heads // size`` and ``kv_heads // size``."""
    b, s = y.shape[0], y.shape[1]
    h, h_kv = cfg.n_heads // size, cfg.kv_heads // size
    hd = cfg.d_model // cfg.n_heads
    qkv = checkpoint_name(y @ blk["wqkv"], "qkv")
    q = qkv[..., :h * hd].reshape(b, s, h, hd)
    k = qkv[..., h * hd:(h + h_kv) * hd].reshape(b, s, h_kv, hd)
    v = qkv[..., (h + h_kv) * hd:].reshape(b, s, h_kv, hd)
    if cfg.rope:
        if positions is None:
            raise ValueError("cfg.rope requires the caller's positions")
        q = _rope_rotate(cfg, q, positions)
        k = _rope_rotate(cfg, k, positions)
    return q, k, v


def embed_tokens(cfg: TransformerConfig, params, tokens):
    """The embedding rows of ``tokens``, times ``cfg.embed_scale``
    where it is not 1 (the product in at least float32, rounded once):
    what every forward pass starts from, training and serving."""
    x = params["embed"][tokens]
    if cfg.embed_scale == 1.0:
        return x
    return (x.astype(jnp.promote_types(x.dtype, jnp.float32))
            * cfg.embed_scale).astype(x.dtype)


def gqa_project(spec: GQA, p, y, positions):
    """The projections of a :class:`GQA` mixer on the normed input ``y``
    ``(b, s, d)``, the ONE place they live (the training forward and the
    serving walk both come through here): ``(q, k, v, g)`` with ``q``
    ``(b, s, n_heads, head_dim)``, ``k`` and ``v`` ``(b, s, n_kv_heads,
    head_dim)``, queries and keys normed head by head (``qk_norm``) and
    then rotated by ``positions`` (``rope``; ``(s,)`` or ``(b, s)``), so
    that ``k`` and ``v`` are what a serving cache row holds; and ``g``
    ``(b, s, n_heads, head_dim)`` the gate before its sigmoid, ``None``
    without one."""
    b, s, _ = y.shape
    h, h_kv, hd = spec.n_heads, spec.n_kv_heads, spec.head_dim
    qkv = y @ p["wqkv"]
    q = qkv[..., :h * hd].reshape(b, s, h, hd)
    k = qkv[..., h * hd:(h + h_kv) * hd].reshape(b, s, h_kv, hd)
    v = qkv[..., (h + h_kv) * hd:(h + 2 * h_kv) * hd].reshape(
        b, s, h_kv, hd)
    g = qkv[..., (h + 2 * h_kv) * hd:].reshape(b, s, h, hd) \
        if spec.gate else None
    if spec.qk_norm:
        q, k = _rms_norm(q, p["q_norm"]), _rms_norm(k, p["k_norm"])
    if spec.rope:
        if positions is None:
            raise ValueError("GQA(rope=True) requires the caller's positions")
        q = _rope_rotate(spec, q, positions)
        k = _rope_rotate(spec, k, positions)
    return q, k, v, g


def gqa_out(spec: GQA, p, o, g):
    """What follows a :class:`GQA` mixer's attention: ``o`` ``(..., n_heads,
    head_dim)`` under the gate ``sigmoid(g)`` (the product in at least
    float32, rounded once) where the mixer has one, then the output
    projection: ``(..., d)``."""
    if g is not None:
        ct = jnp.promote_types(o.dtype, jnp.float32)
        o = (o.astype(ct) * jax.nn.sigmoid(g.astype(ct).reshape(o.shape))
             ).astype(o.dtype)
    return o.reshape(*o.shape[:-2], -1) @ p["wo"]


def gqa_scope(spec: GQA):
    """The scope a :class:`GQA` mixer's attention itself runs under,
    inside the mixer's ``layer_scope("attn")``: ``attn_window`` or
    ``attn_full`` (cache write and read on the serving path)."""
    return layer_scope("attn_window" if spec.window else "attn_full")


def _gqa_mixer(spec: GQA, p, y, positions):
    """Attention stated on the layer, on the normed input ``y``: the
    flash path under the layer's own window."""
    q, k, v, g = gqa_project(spec, p, y, positions)
    with gqa_scope(spec):
        o = flash_attention(q, k, v, causal=True, window=spec.window)
    return gqa_out(spec, p, o, g)


@jax.custom_vjp
def _causal_conv(x, w):
    """Depthwise causal convolution over the sequence: ``out_t = sum_j
    w[j] x_{t - (taps - 1) + j}``, zeros before the sequence's start.
    ``x`` (b, s, c), ``w`` (taps, c).  Sums in float32; only ``x`` and
    ``w`` are kept for the way back, which is the same sum run the other
    way (autodiff would keep a float32 copy of ``x`` per tap)."""
    return _shifted_sum(x, w, lead=True)


def _shifted_sum(x, w, lead: bool):
    """``sum_j w[j] * (x shifted by taps - 1 - j)``: towards later
    positions (``lead``, the convolution) or towards earlier ones (its
    adjoint, with the taps reversed)."""
    taps, s = w.shape[0], x.shape[1]
    ct = jnp.promote_types(x.dtype, jnp.float32)
    pad = (taps - 1, 0) if lead else (0, taps - 1)
    xp = jnp.pad(x, ((0, 0), pad, (0, 0))).astype(ct)
    return sum(xp[:, j:j + s] * w[j].astype(ct)
               for j in range(taps)).astype(x.dtype)


def _causal_conv_bwd(res, g):
    x, w = res
    taps, s = w.shape[0], x.shape[1]
    ct = jnp.promote_types(x.dtype, jnp.float32)
    xp = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    dw = jnp.stack([jnp.sum(xp[:, j:j + s].astype(ct) * g.astype(ct),
                            axis=(0, 1)) for j in range(taps)])
    return _shifted_sum(g, w[::-1], lead=False), dw.astype(w.dtype)


_causal_conv.defvjp(lambda x, w: (_causal_conv(x, w), (x, w)),
                    _causal_conv_bwd)


def _l2_norm(x):
    ct = jnp.promote_types(x.dtype, jnp.float32)
    xf = x.astype(ct)
    return (xf * jax.lax.rsqrt(jnp.sum(xf * xf, -1, keepdims=True)
                               + 1e-6)).astype(x.dtype)


def _kda_mixer(spec: KDA, p, y):
    """Kimi Delta Attention on the normed input ``y`` (b, s, d): silu of
    a short causal convolution on q, k and v, q and k l2-normalised per
    head, a per-channel log-decay ``-exp(a_log) softplus(low-rank(y) +
    dt_bias)`` and a write strength ``sigmoid(y wb)`` in float32, the
    chunked delta rule, then a per-head rmsnorm under a sigmoid gate."""
    b, s, _ = y.shape
    h, hd = spec.n_heads, spec.head_dim
    ct = jnp.promote_types(y.dtype, jnp.float32)
    heads = lambda t: t.reshape(b, s, h, hd)
    q, k, v = map(heads, jnp.split(
        jax.nn.silu(_causal_conv(y @ p["wqkv"], p["conv"])), 3, axis=-1))
    rate = jax.nn.softplus(((y @ p["wf1"]) @ p["wf2"]).astype(ct)
                           + p["dt_bias"].astype(ct))
    g = -jnp.exp(p["a_log"].astype(ct))[:, None] * heads(rate)
    beta = jax.nn.sigmoid((y @ p["wb"]).astype(ct))
    # Named so that a rematerialised layer keeps it (_SAVED_IN_REMAT):
    # the chunked rule's backward runs a forward of its own from the
    # rule's inputs, and the region would otherwise run the rule once
    # more for an output it only hands on.
    o = checkpoint_name(kda_chunked(_l2_norm(q), _l2_norm(k), v, g, beta),
                        "kda_out")
    gate = jax.nn.sigmoid(heads(((y @ p["wg1"]) @ p["wg2"]).astype(ct)))
    o = (_rms_norm(o.astype(ct), p["norm"]) * gate).astype(y.dtype)
    return o.reshape(b, s, h * hd) @ p["wo"]


def mamba2_project(spec: Mamba2, p, y):
    """The input projection of a Mamba-2 mixer on the normed input ``y``
    ``(b, s, d)``, one fused product cut as ``[z | xBC | dt]``: the gate
    ``z`` ``(b, s, d_inner)``, the convolution's input ``xBC`` ``(b, s,
    conv_dim)`` and the step sizes before their bias and softplus ``dt``
    ``(b, s, n_heads)``."""
    proj = y @ p["in_proj"]
    di, dc = spec.d_inner, spec.conv_dim
    return proj[..., :di], proj[..., di:di + dc], proj[..., di + dc:]


def mamba2_scan(spec: Mamba2, p, xBC, dt, entry=None):
    """The convolution and the recurrence of a Mamba-2 mixer, from what
    a sequence kept: ``entry`` is ``{"h": (b, n_heads, head_dim,
    d_state) float32, "conv": (b, conv - 1, conv_dim)}``, the state and
    the convolution's last inputs before this pass (``None``: a sequence
    that starts here, zeros both).  ``xBC`` ``(b, s, conv_dim)`` and
    ``dt`` ``(b, s, n_heads)`` are :func:`mamba2_project`'s.  Returns
    ``(y (b, s, d_inner), entry)`` with the entry as the pass leaves it:
    the state after the last token and the last ``conv - 1`` inputs (the
    old ones still among them where the pass is shorter).

    A pass of one token with an entry is a decode step and runs
    :func:`~mpi4torch_tpu.ops.ssd.ssd_step`, every sequence's state read
    once and written once; every other pass runs the chunked form from
    the entry's state."""
    b, s, _ = xBC.shape
    taps, ct = spec.conv, jnp.promote_types(xBC.dtype, jnp.float32)
    tail = jnp.zeros((b, taps - 1, spec.conv_dim), xBC.dtype) \
        if entry is None else entry["conv"].astype(xBC.dtype)
    seen = jnp.concatenate([tail, xBC], axis=1)
    conv = sum(seen[:, j:j + s].astype(ct) * p["conv"][j].astype(ct)
               for j in range(taps)) + p["conv_bias"].astype(ct)
    conv = jax.nn.silu(conv).astype(xBC.dtype)
    g, n = spec.n_groups, spec.d_state
    x = conv[..., :spec.d_inner].reshape(b, s, spec.n_heads, spec.head_dim)
    B = conv[..., spec.d_inner:spec.d_inner + g * n].reshape(b, s, g, n)
    C = conv[..., spec.d_inner + g * n:].reshape(b, s, g, n)
    delta = jax.nn.softplus(dt.astype(ct) + p["dt_bias"].astype(ct))
    A = -jnp.exp(p["a_log"].astype(ct))
    if entry is not None and s == 1:
        y, h = ssd_step(entry["h"], x[:, 0], delta[:, 0], A, B[:, 0],
                        C[:, 0], p["d"])
        y = y[:, None]
    else:
        y, h = ssd_chunked(x, delta, A, B, C, p["d"],
                           None if entry is None else entry["h"],
                           chunk=spec.chunk)
    return y.reshape(b, s, spec.d_inner), \
        {"h": h, "conv": seen[:, -(taps - 1):]}


def mamba2_out(spec: Mamba2, p, y, z):
    """What follows the recurrence: ``y`` under the gate ``silu(z)``,
    an rmsnorm over each group's ``d_inner / n_groups`` channels with
    one scale of ``d_inner`` (gate first, then norm), the output
    projection.  ``y`` and ``z`` ``(b, s, d_inner)``."""
    ct = jnp.promote_types(y.dtype, jnp.float32)
    gated = y.astype(ct) * jax.nn.silu(z.astype(ct))
    groups = gated.reshape(*gated.shape[:-1], spec.n_groups, -1)
    ms = jnp.mean(jnp.square(groups), axis=-1, keepdims=True)
    normed = (groups * jax.lax.rsqrt(ms + 1e-5)).reshape(gated.shape) \
        * p["norm"]["scale"].astype(ct)
    return normed.astype(y.dtype) @ p["out_proj"]


_MLA_BLOCK = 2048


def _blockwise_causal_attention(q, k, v, block: int):
    """Causal attention over the whole sequence as a triangle of
    ``block`` x ``block`` calls of the flash block primitive, the partials
    of a query block merged exactly (``merge_partials``).  Written for
    head sizes and lengths at which one call did not fit the kernels: a
    192-wide key is staged 256 wide, and at 8,192 of them Mosaic refused
    the forward's staging (16.38 MB of scoped VMEM against its default
    16) while the backward kernels declined 8,192 queries.  At 2,048 all
    three kernels run.  Since ``flash.tile_plan`` asks Mosaic for what
    the kernels stage the one call compiles as well; the triangle stays
    until that call has been measured inside the step."""
    s = q.shape[1]
    if s <= block or s % block:
        return flash_attention(q, k, v, causal=True)
    cut = lambda x, i: x[:, i * block:(i + 1) * block]
    outs = []
    for i in range(s // block):
        out = lse = None
        for j in range(i + 1):
            o_b, lse_b = flash_block_attention(
                cut(q, i), cut(k, j), cut(v, j), causal=True,
                q_offset=i * block, kv_offset=j * block)
            out, lse = (o_b, lse_b) if out is None else \
                merge_partials(out, lse, o_b, lse_b)
        outs.append(out)
    return jnp.concatenate(outs, axis=1)


def mla_project(cfg: TransformerConfig, spec: MLA, p, y, positions):
    """The projections of latent attention on the normed input ``y``
    ``(b, s, d)``, the ONE place they live (the training forward and the
    serving walk both come through here): ``(q, c, k_r)`` with ``q``
    ``(b, s, h, qk_nope + qk_rope)``, ``c`` ``(b, s, kv_rank)`` the
    normed latent and ``k_r`` ``(b, s, qk_rope)`` the key channels all
    heads share.  Under ``spec.rope`` the last ``qk_rope`` channels of
    ``q`` and ``k_r`` are rotated by ``positions`` (``(s,)`` or ``(b,
    s)``).  ``[c ; k_r]`` is everything a later query needs of this
    token: the serving cache's entry.  ``spec.q_scale`` and
    ``spec.kv_scale`` are applied here, once, to the normed latents: the
    ``c`` handed back (and cached) is the scaled one.  Fourth comes
    ``cq`` ``(b, s, q_rank)``, the normed (and scaled) query latent that
    an indexer's queries come up from (:func:`index_project`); ``None``
    without a query rank."""
    b, s, _ = y.shape
    h, dn, dr = spec.n_heads, spec.qk_nope, spec.qk_rope
    # The product in at least float32, rounded once: sqrt(12) is no
    # bfloat16 number.
    scaled = lambda t, by: t if by == 1.0 else (
        t.astype(jnp.promote_types(t.dtype, jnp.float32)) * by
    ).astype(t.dtype)
    cq = None
    if spec.q_rank:
        cq = scaled(_rms_norm(y @ p["wqa"], p["q_norm"]), spec.q_scale)
        q = cq @ p["wq"]
    else:
        q = y @ p["wq"]
    q = q.reshape(b, s, h, dn + dr)
    latent = y @ p["wa"]
    c, k_r = latent[..., :spec.kv_rank], latent[..., spec.kv_rank:]
    c = scaled(_rms_norm(c, p["kv_norm"]), spec.kv_scale)
    if spec.rope:
        if positions is None:
            raise ValueError("MLA(rope=True) requires the caller's positions")
        q = jnp.concatenate(
            [q[..., :dn], _rope_rotate(cfg, q[..., dn:], positions)], axis=-1)
        k_r = _rope_rotate(cfg, k_r[:, :, None, :], positions)[:, :, 0]
    return q, c, k_r, cq


def index_project(cfg: TransformerConfig, ix: Indexer, p, y, cq, positions):
    """The projections of an :class:`Indexer` (leaves ``p``: ``wq``,
    ``wk``, ``k_norm``, ``ww``) on the layer's normed input ``y`` ``(b,
    s, d)`` and normed query latent ``cq`` ``(b, s, q_rank)``, the ONE
    place they live: ``(q_i, k_i, w)`` with ``q_i`` ``(b, s, n_heads,
    head_dim)`` the index queries, ``k_i`` ``(b, s, head_dim)`` the
    token's index key, layer-normed with scale and bias (what the
    serving cache's index-key entry holds), the first ``ix.rope``
    channels of both rotated by ``positions``; and ``w`` ``(b, s,
    n_heads)`` float32, each head's weight in the score with the scale
    ``n_heads ** -0.5 * head_dim ** -0.5`` in it."""
    b, s, _ = y.shape
    q_i = (cq @ p["wq"]).reshape(b, s, ix.n_heads, ix.head_dim)
    k_i = _layer_norm(y @ p["wk"], p["k_norm"])
    if ix.rope:
        r = ix.rope
        q_i = jnp.concatenate(
            [_rope_rotate(cfg, q_i[..., :r], positions), q_i[..., r:]], -1)
        k_i = jnp.concatenate(
            [_rope_rotate(cfg, k_i[:, :, None, :r], positions)[:, :, 0],
             k_i[..., r:]], -1)
    ct = jnp.promote_types(y.dtype, jnp.float32)
    w = (y @ p["ww"]).astype(ct) * (
        float(ix.n_heads) ** -0.5 * float(ix.head_dim) ** -0.5)
    return q_i, k_i, w


_LOWEST = float(jnp.finfo(jnp.float32).min)


def _sortable(x):
    """float32 as uint32 keys of the same order (``-0.0 < +0.0``)."""
    u = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    return jnp.where(u >> 31 == 1, ~u, u | jnp.uint32(1 << 31))


def select_mask(scores, valid, top_k: int):
    """Which positions a query attends: ``scores`` ``(rows, n)`` float32
    and ``valid`` ``(rows, n)`` bool give the ``(rows, n)`` bool mask of
    each row's ``top_k`` valid positions of the largest scores, all the
    valid ones where there are no more; equal scores go to the earlier
    position, as :func:`jax.lax.top_k` breaks them.  Exact, and with no
    sort: the ``top_k``-th largest key of a row is found a bit at a time
    (32 counts over the row), and what lies above it is selected; the
    positions that tie with it are counted off from the left only where
    a row has such a tie to break.  A score of ``-inf`` counts as the
    least float32, in both forms of the selection."""
    key = jnp.where(valid, _sortable(jnp.maximum(scores, _LOWEST)),
                    jnp.uint32(0))
    count = lambda m: jnp.sum(m, axis=-1, dtype=jnp.int32)

    def bit(i, t):
        cand = t | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        return jnp.where(count(key >= cand[:, None]) >= top_k, cand, t)

    # The largest threshold at least top_k keys reach: the top_k-th
    # largest key; 0, which every key reaches, for a row with fewer.
    t = jax.lax.fori_loop(0, 32, bit, jnp.zeros(key.shape[:1], jnp.uint32))
    above = valid & (key > t[:, None])
    ties = valid & (key == t[:, None])
    room = top_k - count(above)

    def counted_off(_):
        return above | (ties & (jnp.cumsum(ties, axis=-1, dtype=jnp.int32)
                                <= room[:, None]))

    return jax.lax.cond(jnp.any(count(ties) > room), counted_off,
                        lambda _: above | ties, None)


_INDEX_BLOCK = 128


def index_select_mask(q_i, k_i, w, top_k: int, q_offset=0):
    """The selection of a whole pass as a mask: ``q_i`` ``(sq, n_heads,
    head_dim)`` at positions ``q_offset, q_offset + 1, ...`` against the
    index keys ``k_i`` ``(sk, head_dim)`` of positions ``0..sk-1`` with
    ``w`` ``(sq, n_heads)``: ``(sq, sk)`` bool, row ``t`` naming the
    ``top_k`` positions at or before its own of the largest index scores
    (``ops.paged_attention.index_scores``, :func:`select_mask`).  A
    block of queries at a time: the scores exist as ``(_INDEX_BLOCK,
    sk)`` float32 and never as ``(sq, sk)``; what is kept is the mask, a
    byte a pair."""
    sq, sk = q_i.shape[0], k_i.shape[0]
    blk = min(_INDEX_BLOCK, sq)
    n = -(-sq // blk)
    pad = lambda x: jnp.pad(x, ((0, n * blk - sq),) + ((0, 0),) * (x.ndim - 1))
    q_i, w = pad(q_i), pad(w)
    kv_pos = jnp.arange(sk, dtype=jnp.int32)

    def block(i):
        cut = lambda x: jax.lax.dynamic_slice_in_dim(x, i * blk, blk, 0)
        q_pos = q_offset + i * blk + jnp.arange(blk, dtype=jnp.int32)
        valid = kv_pos[None, :] <= q_pos[:, None]
        return select_mask(index_scores(cut(q_i), k_i, cut(w)), valid, top_k)

    mask = jax.lax.map(block, jnp.arange(n, dtype=jnp.int32))
    return mask.reshape(n * blk, sk)[:sq]


def select_rows(scores, valid, top_k: int):
    """:func:`select_mask`'s selection as positions, for a read that
    gathers: ``(rows, top_k)`` int32, the selected positions in the
    order of their scores, ``-1`` behind them where a row has fewer than
    ``top_k`` valid ones (:func:`jax.lax.top_k` itself)."""
    # No valid score is -inf (select_mask's rule), so what top_k hands
    # back at -inf is a position that was not valid: no second look-up
    # (a gather of top_k scalars a row costs a TPU 0.3 ms).
    vals, rows = jax.lax.top_k(
        jnp.where(valid, jnp.maximum(scores.astype(jnp.float32), _LOWEST),
                  -jnp.inf), min(top_k, scores.shape[-1]))
    rows = jnp.where(vals > -jnp.inf, rows, -1).astype(jnp.int32)
    if rows.shape[-1] < top_k:
        rows = jnp.pad(rows, ((0, 0), (0, top_k - rows.shape[-1])),
                       constant_values=-1)
    return rows


def mla_expand(spec: MLA, p, c, k_r):
    """Keys and values of every head from the normed latent ``c`` and
    the shared key channels ``k_r``: ``k`` ``(b, s, h, qk_nope +
    qk_rope)`` and ``v`` zero-padded to the same width (zeros add
    nothing to the weighted sum), as the flash kernels take them."""
    b, s, _ = c.shape
    h, dn, dr, dv = spec.n_heads, spec.qk_nope, spec.qk_rope, spec.v_dim
    kv = (c @ p["wb"]).reshape(b, s, h, dn + dv)
    k = jnp.concatenate(
        [kv[..., :dn],
         jnp.broadcast_to(k_r[:, :, None, :], (b, s, h, dr))], axis=-1)
    v = jnp.pad(kv[..., dn:], ((0, 0),) * 3 + ((0, dn + dr - dv),))
    return k, v


def _mla_mixer(cfg: TransformerConfig, spec: MLA, p, y, positions):
    """Latent attention on the normed input ``y``: the flash kernels at
    query-key size ``qk_nope + qk_rope``, the value zero-padded up to
    it."""
    b, s, _ = y.shape
    if spec.index is not None:
        raise CommError(
            "the training forward does not run an indexed mixer "
            f"(MLA.index={spec.index!r}): its indexer is trained by a loss "
            "of its own beside the model's, which is not written; serve "
            "the configuration through mpi4torch_tpu.serve")
    q, c, k_r, _ = mla_project(cfg, spec, p, y, positions)
    k, v = mla_expand(spec, p, c, k_r)
    o = _blockwise_causal_attention(q, k, v, _MLA_BLOCK)[..., :spec.v_dim]
    return o.reshape(b, s, spec.n_heads * spec.v_dim) @ p["wo"]


def refuse_layer_spec(cfg: TransformerConfig, what: str) -> None:
    """The answer of this module's own single-sequence oracle
    (``init_kv_cache``, ``decode_step``, ``prefill``, ``generate``) to a
    configuration with a per-layer spec: it knows one kind of layer.
    The serving engine (``serve/kv.py``) walks the spec itself."""
    if cfg.layers:
        raise CommError(
            f"{what}: the single-sequence oracle knows one kind of layer "
            "and keeps no latent, recurrent or expert state — serve a "
            "per-layer spec through mpi4torch_tpu.serve")


def branch_norm(cfg: TransformerConfig, spec: LayerSpec, blk, out,
                which: str):
    """A branch's output before the residual sum: through the layer's
    second norm (``ln1_post`` after the mixer, ``ln2_post`` after the
    FFN) where the spec states one."""
    return _norm(cfg, out, blk[which]) if spec.post_norm else out


def _ffn_dense(cfg: TransformerConfig, blk, y):
    """The dense FFN product of the normed input ``y``, before the
    residual; with a serving shard's ``w1``/``w2`` it is this rank's
    partial sum."""
    ffn_in = checkpoint_name(y @ blk["w1"], "ffn_in")
    if cfg.ffn == "swiglu":
        gate, up = jnp.split(ffn_in, 2, axis=-1)
        return (jax.nn.silu(gate) * up) @ blk["w2"]
    return jax.nn.gelu(ffn_in) @ blk["w2"]


def dense_ffn(cfg: TransformerConfig, spec: LayerSpec, blk, y):
    """:func:`_ffn_dense` as a spec'd layer runs it: under
    ``layer_scope("ffn")`` where the layer carries or joins a shortcut
    branch (there the dense path is what the branch runs beside, and a
    trace tells the two apart), as it is everywhere else."""
    if not spec.shortcut:
        return _ffn_dense(cfg, blk, y)
    with layer_scope("ffn"):
        return _ffn_dense(cfg, blk, y)


def shortcut_branch(spec: LayerSpec, blk, y, comm_ep=None, live=None):
    """The shortcut branch of a layer that carries one, on the layer's
    ``ln2`` rows ``y``: ``(s, rows, zero_pairs, overflow)`` with ``s`` of
    ``y``'s shape, to be added to the stream by the layer whose spec says
    ``join``, and :func:`~mpi4torch_tpu.parallel.moe.held_experts_ffn`'s
    three counts.  The training forward and the serving walk both come
    through here."""
    with layer_scope("moe"):
        s, *counts = held_experts_ffn(
            y.reshape(-1, y.shape[-1]), blk["branch"], spec.branch,
            comm_ep, live=live)
    return (s.reshape(y.shape), *counts)


def _ffn_residual(cfg: TransformerConfig, blk, x, comm_ep):
    """Post-attention FFN (dense or MoE) with pre-LN and residual; shared
    by the training forward and the decode path.  Returns ``(x, aux)``.

    MoE routing note: capacity competition is over exactly the tokens in
    ``x`` — a whole (batch x seq) call during training/prefill, one
    position's batch during incremental decode.  When capacity binds,
    the two can therefore drop different tokens; teacher-forcing
    equivalence between :func:`forward` and :func:`decode_step` is exact
    whenever capacity does not bind (see :func:`decode_step`)."""
    b_s = x.shape[:-1]
    d = x.shape[-1]
    y = _norm(cfg, x, blk["ln2"])
    if cfg.n_experts > 0:
        flat = y.reshape(-1, d)
        if comm_ep is not None and comm_ep.size > 1:
            ff, aux = moe_ffn(comm_ep, flat, blk["moe"], cfg.capacity)
        else:
            ff, aux = moe_ffn_dense(flat, blk["moe"], cfg.capacity)
        return x + ff.reshape(*b_s, d), aux
    return x + _ffn_dense(cfg, blk, y), jnp.zeros((), x.dtype)


def _zigzag_positions(comm_sp, s_local: int):
    """Global positions of this rank's zigzag sequence shard (symbolic
    rank safe) — by slicing the global position axis with the ONE
    layout-defining helper, so the transformer's position/label math can
    never drift from the data sharding in parallel/attention.py."""
    from ..parallel.attention import zigzag_slice

    return zigzag_slice(
        comm_sp, jnp.arange(comm_sp.size * s_local, dtype=jnp.int32),
        axis=0)


def _attention(q, k, v, comm_sp, attn: str, window: int = 0):
    if attn not in ("dense", "ring", "ulysses", "zigzag"):
        raise ValueError(f"unknown attention strategy {attn!r}")
    if comm_sp is None or comm_sp.size == 1:
        # The fused flash path: Pallas kernel on eligible TPU shapes
        # (scores never hit HBM), jnp otherwise — numerically the same
        # softmax as :func:`dense_attention`, which stays the test oracle.
        return flash_attention(q, k, v, causal=True, window=window)
    if attn == "dense":
        raise ValueError(
            "attn='dense' cannot see across sequence shards: with a "
            "size>1 sequence-parallel communicator each rank would attend "
            "only within its own block (and mask as if it started at "
            "position 0).  Use attn='ring' or attn='ulysses', or pass "
            "comm_sp=None with the full sequence."
        )
    if attn == "ring":
        return ring_attention(comm_sp, q, k, v, causal=True, window=window)
    if attn == "zigzag":
        if window:
            raise ValueError(
                "attn='zigzag' does not compose with attn_window: a "
                "sliding window already balances causal work (every "
                "query sees the same key count), which is the whole "
                "point of the zigzag layout — use attn='ring' for "
                "windowed sequence parallelism")
        return zigzag_ring_attention(comm_sp, q, k, v)
    return ulysses_attention(comm_sp, q, k, v, causal=True, window=window)


def _is_uniform(spec: LayerSpec) -> bool:
    """The configuration's own block: its attention and its FFN."""
    return spec.mixer is None and spec.ffn is None and not spec.only


def _memory_limit_bytes() -> Optional[int]:
    """What the first local device says it may hold, or ``None`` where
    the backend reports no limit (the CPU)."""
    return (jax.local_devices()[0].memory_stats() or {}).get("bytes_limit")


def _remat_kept_layers(cfg: TransformerConfig, tokens_shape, dtype,
                       param_bytes: int, limit_bytes: Optional[int],
                       flash_pair: bool = True) -> int:
    """How many of the stack's uniform blocks keep their named outputs
    (:data:`_KEPT_PRODUCTS`, and the flash pair where ``flash_pair``)
    under ``cfg.remat``: the most whose bytes fit, with three times
    ``param_bytes`` (the parameters, their gradients and the new
    parameters stand beside them), in :data:`_REMAT_ROOM` of
    ``limit_bytes``.  All of them where no limit is reported; the blocks
    past the count keep nothing."""
    n = sum(_is_uniform(s) for s in cfg.layer_specs)
    if limit_bytes is None:
        return n
    hd = cfg.d_model // cfg.n_heads
    width = (cfg.n_heads + 2 * cfg.kv_heads) * hd + cfg.d_model
    if cfg.n_experts == 0:
        width += (2 if cfg.ffn == "swiglu" else 1) * cfg.d_ff
    a_token = width * jnp.dtype(dtype).itemsize
    if flash_pair:
        a_token += cfg.d_model * jnp.dtype(dtype).itemsize + cfg.n_heads \
            * jnp.promote_types(dtype, jnp.float32).itemsize
    room = _REMAT_ROOM * limit_bytes - 3 * param_bytes
    return int(min(n, max(0, room // (math.prod(tokens_shape) * a_token))))


def forward(cfg: TransformerConfig, params, tokens, comm_sp=None,
            attn: str = "ring", comm_ep=None, return_aux: bool = False,
            return_hidden: bool = False):
    """Logits for a (batch, seq_local) shard of token ids.

    ``comm_sp`` is the sequence-parallel communicator (or None for a full
    unsharded sequence); ``tokens`` holds this rank's contiguous sequence
    block, rank-major.  With sp sharding, positional embeddings are indexed
    at *global* positions (rank offset may be a traced ``lax.axis_index``).

    With ``cfg.n_experts > 0`` each block's FFN is the expert-parallel MoE
    (experts sharded over ``comm_ep``; pass None to keep all experts
    local).  ``return_aux`` additionally returns the summed load-balancing
    loss.  ``return_hidden`` returns the post-``ln_f`` hidden states
    (batch, seq_local, d_model) INSTEAD of logits — the unembedding is
    skipped so :func:`lm_loss`'s chunked-vocab path can fold it into the
    online-logsumexp scan without ever materializing the logits.

    With ``cfg.layers`` the loop walks the spec: each layer's mixer and
    FFN are what its :class:`LayerSpec` names, the new kinds each under
    its scope (``mpi4torch.kda`` / ``.mla`` / ``.attn`` / ``.moe``).
    """
    out, aux, _ = _forward(cfg, params, tokens, comm_sp, attn, comm_ep,
                           return_hidden)
    return (out, aux) if return_aux else out


def _forward(cfg: TransformerConfig, params, tokens, comm_sp, attn: str,
             comm_ep, return_hidden: bool):
    """:func:`forward` as ``(out, aux, stats)``: the summed load-balancing
    loss, and what the expert layers of a spec counted (``None`` without
    such a layer): ``moe_rows``, the rows each held expert took in every
    expert layer, ``(expert layers, held)``, and ``moe_overflow_calls``,
    how many of those layers had held rows behind their prefix
    (:func:`~mpi4torch_tpu.parallel.moe.held_experts_ffn`); over an
    expert-parallel communicator also what
    :func:`~mpi4torch_tpu.parallel.moe.exchanged_experts_ffn` counted,
    a row an expert layer: ``ep_rows_sent`` ``(expert layers, ranks)``,
    ``ep_padding_rows`` and ``ep_overflow_rounds``."""
    b, s_local = tokens.shape
    h = cfg.n_heads
    if comm_sp is not None and comm_sp.size > 1:
        if cfg.pos_table and comm_sp.size * s_local > cfg.max_seq:
            # Without this, the positional-table dynamic_slice would
            # clamp the high ranks' start offsets and silently reuse the
            # last positional block.  Under rope there is no table and
            # no cap: positions are computed directly, and training past
            # max_seq is exactly the beyond-table long-context case the
            # relative encoding exists for.
            raise ValueError(
                f"global sequence {comm_sp.size * s_local} (sp="
                f"{comm_sp.size} x s_local={s_local}) exceeds cfg.max_seq "
                f"{cfg.max_seq}")
        offset = jnp.asarray(comm_sp.rank) * s_local
    else:
        offset = 0
    zigzag_sharded = (attn == "zigzag" and comm_sp is not None
                      and comm_sp.size > 1)
    if zigzag_sharded:
        # This rank's tokens are the ZIGZAG shard (chunk r + mirror
        # chunk 2*sp-1-r; parallel.zigzag_slice produces it) — two
        # global position intervals, not one.
        positions = _zigzag_positions(comm_sp, s_local)
    else:
        positions = offset + jnp.arange(s_local, dtype=jnp.int32)
    x = embed_tokens(cfg, params, tokens)
    if cfg.pos_table:
        if zigzag_sharded:
            x = x + jnp.take(params["pos"], positions, axis=0)[None]
        else:
            x = x + jax.lax.dynamic_slice_in_dim(
                params["pos"], offset, s_local, 0)[None]
    d = x.shape[-1]
    aux_total = jnp.zeros((), x.dtype)

    if cfg.layers and comm_sp is not None and comm_sp.size > 1:
        raise CommError(
            "a per-layer spec does not compose with sequence parallelism: "
            "the KDA state and the MLA keys are not carried across "
            "sequence shards")

    def block_fn(x, blk):
        y = _norm(cfg, x, blk["ln1"])
        q, k, v = _split_qkv(cfg, blk, y, positions)
        o = _attention(q, k, v, comm_sp, attn, cfg.attn_window)
        x = checkpoint_name(x + o.reshape(b, s_local, d) @ blk["wo"],
                            "attn_residual")
        x, aux = _ffn_residual(cfg, blk, x, comm_ep)
        return x, aux

    def attention_fn(x, blk):
        # The configuration's own attention as a layer of one part.
        q, k, v = _split_qkv(cfg, blk, _norm(cfg, x, blk["ln1"]), positions)
        o = _attention(q, k, v, comm_sp, attn, cfg.attn_window)
        return x + o.reshape(b, s_local, d) @ blk["wo"]

    def mixer_fn(spec, x, blk):
        if isinstance(spec.mixer, Mamba2):
            raise CommError(
                "the training forward does not run a Mamba2 mixer: the "
                "chunked scan's backward is not written (ops/ssd.py is "
                "inference only); serve the configuration through "
                "mpi4torch_tpu.serve")
        if spec.mixer is None:
            return attention_fn(x, blk)
        y = _norm(cfg, x, blk["ln1"])
        if isinstance(spec.mixer, KDA):
            with layer_scope("kda"):
                return x + branch_norm(cfg, spec, blk, _kda_mixer(
                    spec.mixer, blk["mixer"], y), "ln1_post")
        if isinstance(spec.mixer, GQA):
            with layer_scope("attn"):
                return x + branch_norm(cfg, spec, blk, _gqa_mixer(
                    spec.mixer, blk["mixer"], y, positions), "ln1_post")
        with layer_scope("mla"):
            return x + branch_norm(cfg, spec, blk, _mla_mixer(
                cfg, spec.mixer, blk["mixer"], y, positions), "ln1_post")

    if comm_ep is not None and comm_ep.size > 1 \
            and any(s.shortcut for s in cfg.layers):
        raise CommError(
            "a shortcut branch over an expert-parallel communicator: the "
            "branch's exchange beside the dense path is not written; the "
            "layer's own expert FFN (LayerSpec.ffn) is exchanged")

    def routing_fn(spec, x, blk):
        # The router of a layer that places it before the mixer, on the
        # layer's input: handed to the experts' region behind the mixer.
        with layer_scope("moe"):
            return route_experts(x.reshape(-1, d), blk["experts"], spec.ffn)

    def experts_fn(spec, x, blk, routing=None):
        with layer_scope("moe"):
            y = _norm(cfg, x, blk["ln2"]).reshape(-1, d)
            ff, c = experts_ffn(y, blk["experts"], spec.ffn, comm_ep,
                                routing=routing)
            ff = branch_norm(cfg, spec, blk, ff.reshape(x.shape), "ln2_post")
        return x + ff, [{name: c[k] for k, name in _COUNTED.items()
                         if k in c}]

    def shortcut_fn(spec, x, blk, carried):
        # A layer that carries or joins a shortcut branch: the branch
        # reads the rows the layer's own FFN reads, and joins the stream
        # behind the FFN residual of the layer that says so.
        y = _norm(cfg, x, blk["ln2"])
        taken = []
        if spec.branch is not None:
            carried, rows, _, over = shortcut_branch(spec, blk, y, comm_ep)
            taken.append({"moe_rows": rows, "moe_overflow_calls": over})
        if spec.ffn is None:
            ff = dense_ffn(cfg, spec, blk, y)
        else:
            with layer_scope("moe"):
                ff, rows, _, over = held_experts_ffn(
                    y.reshape(-1, d), blk["experts"], spec.ffn, comm_ep)
                ff = ff.reshape(x.shape)
            taken.append({"moe_rows": rows, "moe_overflow_calls": over})
        x = x + branch_norm(cfg, spec, blk, ff, "ln2_post")
        if spec.join:
            x, carried = x + carried, None
        return x, carried, taken

    # With remat a uniform block is one rematerialised region; a new kind
    # of mixer and an expert FFN are one each, so that the backward holds
    # the temporaries of one of them at a time, not of both.  The first
    # uniform blocks keep their products' outputs and the flash pair as
    # far as the device has the room (_remat_kept_layers), the rest keep
    # nothing; under sequence parallelism no block keeps the pair (every
    # ring step's block would keep its own).  A mixer's or an FFN's
    # region keeps ``kda_out`` alone: the other names do nothing there.
    remat = functools.partial(jax.checkpoint, policy=_SAVED_IN_REMAT) \
        if cfg.remat else (lambda f: f)
    keeps = 0
    if cfg.remat:
        one_rank = comm_sp is None or comm_sp.size == 1
        keeps = _remat_kept_layers(
            cfg, tokens.shape, x.dtype,
            sum(p.size * p.dtype.itemsize for p in jax.tree.leaves(params)),
            _memory_limit_bytes(), flash_pair=one_rank)
        kept = jax.checkpoint_policies.save_only_these_names(
            *_KEPT_PRODUCTS, *(_FLASH_RESIDUALS if one_rank else ()))
    counted, carried = [], None
    for spec, blk in zip(cfg.layer_specs, params["blocks"]):
        if _is_uniform(spec):
            fn = block_fn
            if cfg.remat:
                fn = jax.checkpoint(block_fn,
                                    policy=kept if keeps > 0 else None)
                keeps -= 1
            x, aux = fn(x, blk)
            aux_total = aux_total + aux
            continue
        routing = routing_fn(spec, x, blk) if spec.route_on else None
        if spec.only != "ffn":
            x = remat(functools.partial(mixer_fn, spec))(x, blk)
        if spec.only == "mixer":
            continue
        if spec.shortcut:
            x, carried, taken = remat(functools.partial(shortcut_fn, spec))(
                x, blk, carried)
            counted += taken
        elif spec.ffn is None and spec.post_norm:
            x = remat(lambda x_, blk_: x_ + branch_norm(
                cfg, spec, blk_, _ffn_dense(
                    cfg, blk_, _norm(cfg, x_, blk_["ln2"])),
                "ln2_post"))(x, blk)
        elif spec.ffn is None:
            x, _ = remat(lambda x_, blk_: _ffn_residual(
                cfg, blk_, x_, comm_ep))(x, blk)
        else:
            x, taken = remat(functools.partial(experts_fn, spec))(
                x, blk, routing)
            counted += taken
    x = _norm(cfg, x, params["ln_f"])
    if return_hidden:
        out = x
    else:
        out = x @ params["unembed"]
    if not counted:
        return out, aux_total, None
    stats = {k: jnp.stack([c[k] for c in counted]) for k in counted[0]
             if k != "moe_overflow_calls"}
    stats["moe_overflow_calls"] = sum(c["moe_overflow_calls"]
                                      for c in counted)
    return out, aux_total, stats


def init_kv_cache(cfg: TransformerConfig, batch: int, dtype=jnp.float32):
    """Per-layer K/V cache for incremental decoding, shaped
    ``(batch, max_seq, kv_heads, head_dim)`` — under GQA the cache holds
    only the KV heads (the whole point: at ``n_kv_heads = n_heads/8`` the
    decode-time cache is 8x smaller, which is the HBM-resident state that
    bounds TPU batch size during serving)."""
    refuse_layer_spec(cfg, "init_kv_cache")
    hd = cfg.d_model // cfg.n_heads
    shape = (batch, cfg.max_seq, cfg.kv_heads, hd)
    return [{"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
            for _ in range(cfg.n_layers)]


def decode_step(cfg: TransformerConfig, params, cache, tokens, pos):
    """One incremental decode step: logits for ``tokens`` (batch,) at
    position ``pos`` (scalar, may be traced), updating the KV cache.

    Returns ``(logits (batch, vocab), new_cache)``.  Attention runs the
    query against the full cache buffer with position-based masking
    (causal + ``cfg.attn_window``): slots beyond ``pos`` are masked as
    future, so the static ``max_seq`` buffer needs no length bookkeeping
    — the XLA-native shape discipline (no dynamic shapes, one compiled
    program for every step).  Jit-compatible: drive it under
    ``lax.scan`` (:func:`generate`).

    Teacher-forcing equivalence: feeding the training sequence token by
    token reproduces :func:`forward`'s logits exactly
    (tests/test_transformer.py TestDecoding) — with one carve-out: MoE
    capacity competition is per *call* (see :func:`_ffn_residual`), so
    with ``n_experts > 0`` the equivalence holds only while capacity
    does not bind (decode routes ``batch`` tokens per step vs a whole
    batch x seq during training)."""
    refuse_layer_spec(cfg, "decode_step")
    b = tokens.shape[0]
    try:
        # Concrete positions are checked eagerly: past max_seq the
        # dynamic slice/update would CLAMP — reusing the last positional
        # embedding and overwriting the last cache slot with plausible
        # but wrong results (the same hazard forward() guards).  Traced
        # positions (inside scan/jit) can't be checked here; generate()
        # enforces the bound before tracing.
        if not 0 <= int(pos) < cfg.max_seq:
            raise ValueError(
                f"decode position {int(pos)} out of range: cfg.max_seq "
                f"is {cfg.max_seq}")
    except jax.errors.ConcretizationTypeError:
        pass
    pos = jnp.asarray(pos, jnp.int32)

    x = embed_tokens(cfg, params, tokens)
    if cfg.pos_table:
        x = x + jax.lax.dynamic_slice_in_dim(params["pos"], pos, 1, 0)[0]

    # Sliding-window serving win: with attn_window set, the query only
    # sees its last `window` positions, so attention runs on a
    # position-tracking STATIC slice of the cache (power-of-two bucket
    # >= window, one compiled program for all steps) instead of the full
    # max_seq buffer — each decoded token costs O(window), not
    # O(max_seq).  Without a window the full buffer is the visible set.
    win = cfg.attn_window
    bucket = cfg.max_seq
    if win:
        bucket = 1
        while bucket < win:
            bucket *= 2
        bucket = min(bucket, cfg.max_seq)

    new_cache = []
    for blk, c in zip(params["blocks"], cache):
        y = _norm(cfg, x, blk["ln1"])
        q, k_new, v_new = _split_qkv(cfg, blk, y[:, None, :], pos[None])
        # The cache dtype is authoritative (it may be an override, e.g. a
        # bf16 serving cache under f32 params — ADVICE r4): cast the
        # projected k/v to it before the in-place update.
        ck = jax.lax.dynamic_update_slice_in_dim(
            c["k"], k_new.astype(c["k"].dtype), pos, 1)
        cv = jax.lax.dynamic_update_slice_in_dim(
            c["v"], v_new.astype(c["v"].dtype), pos, 1)
        new_cache.append({"k": ck, "v": cv})
        if bucket < cfg.max_seq:
            # Earliest slice start that still covers [pos-win+1, pos];
            # in-window masking inside the kernel does the rest.
            start = jnp.clip(pos - bucket + 1, 0, cfg.max_seq - bucket)
            kk = jax.lax.dynamic_slice_in_dim(ck, start, bucket, 1)
            vv = jax.lax.dynamic_slice_in_dim(cv, start, bucket, 1)
            kv_off = start
        else:
            kk, vv, kv_off = ck, cv, 0
        o, _ = flash_block_attention(
            q, kk, vv, causal=True, q_offset=pos, kv_offset=kv_off,
            window=win, impl="jnp")
        x = x + o.reshape(b, cfg.d_model).astype(x.dtype) @ blk["wo"]
        x, _ = _ffn_residual(cfg, blk, x, None)
    x = _norm(cfg, x, params["ln_f"])
    return x @ params["unembed"], new_cache


def prefill(cfg: TransformerConfig, params, cache, prompt):
    """Populate the KV cache from a whole prompt in ONE batched pass (the
    training forward's compute shape — MXU-sized matmuls over the full
    prompt — rather than prompt_len sequential single-token steps) and
    return ``(last_logits (batch, vocab), new_cache)``."""
    refuse_layer_spec(cfg, "prefill")
    b, p_len = prompt.shape
    x = embed_tokens(cfg, params, prompt)
    if cfg.pos_table:
        x = x + params["pos"][None, :p_len]
    new_cache = []
    for blk, c in zip(params["blocks"], cache):
        y = _norm(cfg, x, blk["ln1"])
        q, k, v = _split_qkv(cfg, blk, y,
                             jnp.arange(p_len, dtype=jnp.int32))
        # Cache dtype is authoritative (possible serving override; see
        # decode_step) — attention itself runs on the params-dtype k/v
        # of this very pass, so prefill logits are unaffected by a
        # lower-precision cache.
        ck = jax.lax.dynamic_update_slice_in_dim(
            c["k"], k.astype(c["k"].dtype), 0, 1)
        cv = jax.lax.dynamic_update_slice_in_dim(
            c["v"], v.astype(c["v"].dtype), 0, 1)
        new_cache.append({"k": ck, "v": cv})
        o = flash_attention(q, k, v, causal=True, window=cfg.attn_window)
        x = x + o.reshape(b, p_len, cfg.d_model) @ blk["wo"]
        x, _ = _ffn_residual(cfg, blk, x, None)
    x = _norm(cfg, x, params["ln_f"])
    return x[:, -1] @ params["unembed"], new_cache


def _select_token(logits, key, temperature: float, top_k: int, dtype):
    """One decoding choice from (batch, vocab) logits: greedy when
    ``temperature == 0``, else categorical sampling at the given
    temperature, optionally restricted to the ``top_k`` highest logits
    (0 = no restriction)."""
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(dtype)
    if top_k:
        kth = jax.lax.top_k(logits, top_k)[0][:, -1:]
        logits = jnp.where(logits >= kth, logits, -jnp.inf)
    return jax.random.categorical(
        key, logits / temperature, axis=-1).astype(dtype)


def select_token(logits, key, temperature: float, top_k: int, dtype):
    """Public decoding-choice rule — THE sampling function of
    :func:`generate`, exported so the serving engine
    (:mod:`mpi4torch_tpu.serve`) samples every slot with the identical
    rule and key discipline: engine-vs-``generate()`` token parity holds
    by construction rather than by parallel edits."""
    return _select_token(logits, key, temperature, top_k, dtype)


def generate(cfg: TransformerConfig, params, prompt, n_new: int,
             dtype=None, temperature: float = 0.0, top_k: int = 0,
             key=None):
    """Autoregressive decoding: prefill the cache from ``prompt``
    (batch, prompt_len) in one batched pass, then emit ``n_new`` tokens
    incrementally.

    ``temperature == 0`` (default) is greedy argmax; ``temperature > 0``
    samples categorically (requires ``key``), optionally from only the
    ``top_k`` highest-logit tokens.  Generation is a single ``lax.scan``
    over :func:`decode_step` (each emitted token fed back in): every
    step within a generation shares one compiled step program (a
    distinct ``n_new`` still traces a new scan — fix the serving-side
    token budget to avoid recompiles).  The cache dtype follows the
    parameters unless ``dtype`` overrides it.  Returns
    (batch, prompt_len + n_new) tokens."""
    refuse_layer_spec(cfg, "generate")
    b, p_len = prompt.shape
    if p_len + n_new > cfg.max_seq:
        raise ValueError(
            f"prompt {p_len} + n_new {n_new} exceeds max_seq "
            f"{cfg.max_seq}")
    if temperature < 0:
        raise ValueError(f"temperature must be >= 0, got {temperature}")
    if top_k < 0 or top_k > cfg.vocab:
        raise ValueError(
            f"top_k must be in [0, vocab={cfg.vocab}], got {top_k}")
    if temperature > 0 and key is None:
        raise ValueError("temperature > 0 requires a PRNG `key`")
    if n_new == 0:
        return prompt
    if dtype is None:
        dtype = params["embed"].dtype
    if key is None:
        key = jax.random.PRNGKey(0)  # unused on the greedy path

    logits, cache = prefill(cfg, params, init_kv_cache(cfg, b, dtype),
                            prompt)
    key, sub = jax.random.split(key)
    first = _select_token(logits, sub, temperature, top_k, prompt.dtype)

    # Each step feeds the token at position i and emits position i+1's
    # choice; feeding stops one short of the final position — the last
    # emitted token needs no decode of its own.
    def step(carry, i):
        cache, tok, key = carry
        logits, cache = decode_step(cfg, params, cache, tok, i)
        key, sub = jax.random.split(key)
        nxt = _select_token(logits, sub, temperature, top_k, prompt.dtype)
        return (cache, nxt, key), nxt

    (_, _, _), rest = jax.lax.scan(
        step, (cache, first, key),
        jnp.arange(p_len, p_len + n_new - 1, dtype=jnp.int32))
    gen = jnp.concatenate([first[None], rest], axis=0)   # (n_new, b)
    return jnp.concatenate([prompt, gen.T], axis=1)


def _chunked_ce(x, unembed, labels, vocab_chunk: int):
    """Per-token cross entropy ``logsumexp(z) - z[label]`` computed in
    vocab chunks under ``lax.scan``: the full (batch, seq, vocab) logits
    array never materializes — each step computes one (batch, seq,
    chunk) slab, folds it into a running online logsumexp, and picks the
    label logit if it falls in the chunk.  At the flagship config
    (vocab 32768, bf16) the dense logits alone are ~1 GiB of HBM per
    step; chunking caps the transient at chunk/vocab of that, and the
    backward rebuilds each slab from the O(d) residuals (XLA transposes
    the scan), trading one extra chunk matmul for the memory."""
    V = unembed.shape[1]
    n_chunks = V // vocab_chunk
    # The online logsumexp runs in at-least-f32 (bf16 running sums would
    # lose the tail mass the chunking is supposed to preserve exactly).
    ct = jnp.promote_types(x.dtype, jnp.float32)
    neg = jnp.asarray(-1e30, ct)
    m0 = jnp.full(labels.shape, neg, ct)
    se0 = jnp.zeros(labels.shape, ct)
    zt0 = jnp.zeros(labels.shape, ct)

    # checkpoint: without it the scan's VJP stacks each step's
    # (b, s, chunk) slab intermediates across ALL chunks — at the
    # flagship config that is ~2 GiB f32, i.e. WORSE than the dense
    # logits this function exists to avoid.  Rematerializing recomputes
    # one chunk matmul per backward step from the O(d) residuals
    # instead (same trade as the per-block remat at cfg.remat).
    @jax.checkpoint
    def body(carry, c):
        m, se, zt = carry
        w = jax.lax.dynamic_slice_in_dim(unembed, c * vocab_chunk,
                                         vocab_chunk, 1)
        z = (x @ w).astype(ct)                       # (b, s, chunk)
        m_new = jnp.maximum(m, jnp.max(z, axis=-1))
        se = se * jnp.exp(m - m_new) + \
            jnp.sum(jnp.exp(z - m_new[..., None]), axis=-1)
        lo = c * vocab_chunk
        in_chunk = (labels >= lo) & (labels < lo + vocab_chunk)
        idx = jnp.clip(labels - lo, 0, vocab_chunk - 1)
        zsel = jnp.take_along_axis(z, idx[..., None], axis=-1)[..., 0]
        zt = jnp.where(in_chunk, zsel, zt)
        return (m_new, se, zt), None

    (m, se, zt), _ = jax.lax.scan(
        body, (m0, se0, zt0), jnp.arange(n_chunks, dtype=jnp.int32))
    return m + jnp.log(se) - zt


def lm_loss(cfg: TransformerConfig, params, tokens, comm_sp=None,
            attn: str = "ring", seq_global: Optional[int] = None,
            comm_ep=None, vocab_chunk: int = 0):
    """Mean next-token cross-entropy over the GLOBAL sequence.

    The label for a shard's last token lives on the next sp rank — it is
    fetched with a one-element ``ring_shift`` (the boundary token rides the
    same differentiable transport as attention K/V; no gradient flows to a
    label, but the collective must appear in every rank's program —
    SURVEY.md §3.3).  The final global position has no successor and is
    masked out; the sp-summed loss is normalized by the static global token
    count."""
    return _lm_loss(cfg, params, tokens, comm_sp, attn, seq_global, comm_ep,
                    vocab_chunk)[0]


def _lm_loss(cfg: TransformerConfig, params, tokens, comm_sp, attn: str,
             seq_global, comm_ep, vocab_chunk: int):
    """:func:`lm_loss` as ``(loss, stats)``, with :func:`_forward`'s
    counts."""
    b, s_local = tokens.shape
    sp = comm_sp.size if comm_sp is not None else 1
    s_global = seq_global or sp * s_local
    if vocab_chunk and (vocab_chunk <= 0
                        or cfg.vocab % vocab_chunk != 0):
        raise ValueError(
            f"vocab_chunk={vocab_chunk} must divide vocab={cfg.vocab}")

    want_hidden = bool(vocab_chunk) and vocab_chunk < cfg.vocab
    out, aux, stats = _forward(cfg, params, tokens, comm_sp, attn, comm_ep,
                               want_hidden)
    if cfg.n_experts == 0:
        aux = None

    if sp > 1 and attn == "zigzag":
        # Zigzag shard = chunks (r, 2*sp-1-r).  Each chunk's last label
        # is the FIRST token of the globally-next chunk: chunk r+1 is
        # rank r+1's lo chunk (ring shift -1) except for the last rank,
        # whose lo chunk is followed by its OWN hi chunk; chunk 2*sp-r
        # is rank r-1's hi chunk (ring shift +1) — rank 0's hi chunk is
        # the global tail, already masked below.  Both shifts appear in
        # every rank's program (SPMD-symmetric), the where picks.
        c = s_local // 2
        lo, hi = tokens[:, :c], tokens[:, c:]
        r = jnp.asarray(comm_sp.rank)
        from_next_lo = ring_shift(comm_sp, lo[:, :1], shift=-1)
        from_prev_hi = ring_shift(comm_sp, hi[:, :1], shift=1)
        lo_last = jnp.where(r == sp - 1, hi[:, :1], from_next_lo)
        labels = jnp.concatenate(
            [lo[:, 1:], lo_last, hi[:, 1:], from_prev_hi], axis=1)
        global_pos = _zigzag_positions(comm_sp, s_local)
    elif sp > 1:
        nxt = ring_shift(comm_sp, tokens[:, :1], shift=-1)
        labels = jnp.concatenate([tokens[:, 1:], nxt], axis=1)
        global_pos = jnp.asarray(comm_sp.rank) * s_local \
            + jnp.arange(s_local)
    else:
        labels = jnp.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1)
        global_pos = jnp.arange(s_local)
    mask = (global_pos < s_global - 1).astype(out.dtype)

    if want_hidden:
        ce = _chunked_ce(out, params["unembed"], labels, vocab_chunk)
    else:
        logp = jax.nn.log_softmax(out, axis=-1)
        ce = -jnp.take_along_axis(logp, labels[..., None],
                                  axis=-1)[..., 0]
    local_sum = jnp.sum(ce * mask[None, :])
    if sp > 1:
        # compression=False on internal sums: softmax denominators, aux
        # stats and loss averages are numerical internals with exact-
        # parity contracts — a user gradient-compression scope must
        # not reach them.
        total = comm_sp.Allreduce(local_sum, MPI_SUM, compression=False)
    else:
        total = local_sum
    loss = total / (b * (s_global - 1))
    if aux is not None:
        if sp > 1:
            # Each sp rank's aux reflects only its own sequence shard's
            # routing; average it so the loss stays rank-identical (the
            # lock-step invariant every collective loss must keep).
            aux = comm_sp.Allreduce(aux, MPI_SUM, compression=False) / sp
        loss = loss + cfg.aux_coef * aux
    return loss, stats


def zero_train_step(cfg: TransformerConfig, params, tokens, opt,
                    opt_state, comm_dp, comm_sp=None, attn: str = "ring",
                    comm_ep=None):
    """One optimizer step with ZeRO-1 sharded state over the dp axis;
    returns ``(loss, new_params, new_opt_state)``.

    The data-parallel reduction moves out of the loss and into
    :func:`~mpi4torch_tpu.parallel.zero.zero_step`'s reduce-scatter:
    each dp rank differentiates its LOCAL mean loss (no dp
    param-averaging, no dp loss-Allreduce — the un-reduced gradients
    are exactly what the reduce-scatter sums), the element-wise ``opt``
    update runs on this rank's 1/dp parameter shard, and the allgather
    re-replicates.  Sequence parallelism composes unchanged inside the
    local loss (the sp discipline of :func:`train_step`).  Trajectories
    match replicated-DP optax training exactly
    (tests/test_transformer.py); optimizer-state HBM is 1/dp of
    replicated — with Adam at scale, the dominant memory term.

    The ep axis composes like in :func:`train_step` (a data axis with
    the param-averaging adjoint + loss averaging), so every dp rank's
    local gradient is already ep-consistent before the dp
    reduce-scatter."""

    def local_loss(p):
        if comm_sp is not None and comm_sp.size > 1:
            p = all_average_tree(comm_sp, p)
        if comm_ep is not None and comm_ep.size > 1:
            p = all_average_tree(comm_ep, p)
        loss = lm_loss(cfg, p, tokens, comm_sp, attn, comm_ep=comm_ep)
        if comm_ep is not None and comm_ep.size > 1:
            loss = comm_ep.Allreduce(loss, MPI_SUM, compression=False) / comm_ep.size
        return loss

    loss, grads = jax.value_and_grad(local_loss)(params)
    # zero_step's reduce-scatter/size turns the un-reduced local grads
    # into the dp-MEAN gradient shard — the same mean the plain recipe's
    # Allreduce/size produces (no scaling here, or it would double).
    new_params, new_state = zero_step(comm_dp, opt, params, grads,
                                      opt_state)
    # Report the dp-global mean loss.
    loss = comm_dp.Allreduce(loss, MPI_SUM, compression=False) / comm_dp.size
    return loss, new_params, new_state


def zero3_train_step(cfg: TransformerConfig, p_shards, template, tokens,
                     opt, opt_state, comm_dp, comm_sp=None,
                     attn: str = "ring"):
    """One optimizer step with ZeRO-3 over the dp axis: the parameters
    live as 1/dp flat shards BETWEEN steps (parameter + optimizer HBM
    both / dp); returns ``(loss, new_p_shards, new_opt_state)``.

    The forward gathers shards on use (:func:`parallel.zero3_params`);
    the backward reduce-scatters the gradients through the Allgather
    adjoint — the dp reduction needs no explicit collective at all.
    Sequence parallelism composes inside the local loss exactly as in
    :func:`zero_train_step`.  Obtain ``(p_shards, opt_state)`` from
    :func:`parallel.zero3_init` and full parameters for evaluation from
    :func:`parallel.zero3_params`; trajectories match replicated-DP
    optax training exactly (tests/test_transformer.py)."""

    def local_loss(p):
        if comm_sp is not None and comm_sp.size > 1:
            p = all_average_tree(comm_sp, p)
        return lm_loss(cfg, p, tokens, comm_sp, attn)

    loss, new_shards, new_state = zero3_step(
        comm_dp, opt, p_shards, template, local_loss, opt_state)
    loss = comm_dp.Allreduce(loss, MPI_SUM, compression=False) / comm_dp.size
    return loss, new_shards, new_state


def held_expert_leaf(path) -> bool:
    """Whether a parameter's path names a leaf that an expert-parallel
    rank holds for itself: ``w1`` or ``w2`` of a spec'd layer's
    ``experts``."""
    keys = [getattr(k, "key", None) for k in path]
    return keys[-2:] in (["experts", "w1"], ["experts", "w2"])


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _scale_cotangent(x, scale: float):
    return x


_scale_cotangent.defvjp(lambda x, scale: (x, None),
                        lambda scale, _, g: (g * scale,))


def ep_average_tree(cfg: TransformerConfig, comm_ep, params):
    """How :func:`train_step`'s parameters enter a loss over the ep
    axis: the leaves every rank holds alike through
    :func:`~mpi4torch_tpu.parallel.dp.replicated_tree` (as they are;
    their cotangents averaged over the axis); where the configuration
    has a per-layer spec, a rank's own experts
    (:func:`held_expert_leaf`) pass as they are too, their cotangent
    times ``1 / ep``: the adjoint exchange has summed it over the axis,
    the average divides the others'."""
    if not any(s.ffn is not None for s in cfg.layers):
        return replicated_tree(comm_ep, params)
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    own = [held_expert_leaf(path) for path, _ in flat]
    alike = iter(replicated_tree(
        comm_ep, [leaf for (_, leaf), o in zip(flat, own) if not o]))
    return jax.tree.unflatten(treedef, [
        _scale_cotangent(leaf, 1.0 / comm_ep.size) if o else next(alike)
        for (_, leaf), o in zip(flat, own)])


def train_step(cfg: TransformerConfig, params, tokens, comm_sp=None,
               comm_dp=None, attn: str = "ring", lr: float = 1e-2,
               comm_ep=None, return_stats: bool = False):
    """One SGD step; returns (loss, new_params), and with
    ``return_stats`` (loss, new_params, stats): this step's routing
    counters, ``{"moe_rows": (expert layers, held), "moe_overflow_calls":
    int32}``, the rows each held expert of a per-layer spec took and how
    many of the expert layers had held rows behind their prefix
    (:func:`~mpi4torch_tpu.parallel.moe.held_experts_ffn`; empty without
    an expert layer), and over an expert-parallel communicator the
    exchange's (:func:`_forward`).

    The replicated parameters enter the loss through
    :func:`~mpi4torch_tpu.parallel.dp.replicated_tree` on every axis
    (dp, sp, ep): as they are, nothing sent, and with the adjoint of the
    reference's parameter-averaging Allreduce (doc/examples.rst:46-65),
    so each rank's gradient still comes out as the mean over the axis,
    the same bits on every rank, and the loss is Allreduce-averaged over
    dp as in the recipe.  The recipe's forward average is the identity
    here, at an all-reduce of every parameter a step and a second copy
    of them: the replicas ARE equal on the way in.  ``run_spmd`` hands
    every rank the same arrays, and every step ends in ``p - lr * g`` on
    a ``g`` that one all-reduce gave every rank in the same bits, so a
    step that starts on equal replicas leaves equal replicas.  That is this
    function's contract with its caller; one whose replicas may differ
    averages them first
    (:func:`~mpi4torch_tpu.parallel.dp.all_average_tree`, which is the
    reference's recipe and makes them equal every forward pass).

    On the sp axis the adjoint's ``1 / sp`` is load-bearing: the
    sp-summed loss (``Allreduce_sp`` in :func:`lm_loss`, with no
    ``1/sp``) scales each rank's cotangents by ``sp``, and only the mean
    in the parameters' adjoint cancels it, the same trick as the
    reference's DP example, applied per axis.
    Jittable end-to-end — on a 2D mesh the whole step is one XLA program
    mixing psum (dp/sp), the ppermute ring and masked collectives.

    The ep axis is treated as a *data* axis with the same recipe (ep ranks
    hold different token shards): the parameters' cotangents are averaged
    over ep and the loss is ep-averaged too.  This keeps every replicated
    leaf — gate, embeddings, attention, and the (logically replicated)
    expert tensors that :func:`~mpi4torch_tpu.parallel.moe.moe_ffn` slices
    per rank — in lock-step, and makes gradients match the dense
    single-rank oracle (tests/test_transformer.py): adjoint-Allreduce sums
    each rank's cotangents, and an expert block's whole-mesh gradient
    already accumulates on its owner rank via the adjoint Alltoall.

    The expert leaves of a per-layer spec (``blocks[i]["experts"]["w1"]``,
    ``["w2"]``) are NOT replicated over ep: each rank passes its own
    ``n_experts / ep`` experts (:func:`~mpi4torch_tpu.parallel.moe.
    exchanged_experts_ffn`), so they are left out of the ep mean
    (:func:`ep_average_tree`) and their gradient, which the adjoint
    exchange has already summed over every rank's tokens, is divided by
    ``ep`` as the mean divides the others'."""

    def global_loss(p):
        if comm_dp is not None and comm_dp.size > 1:
            p = replicated_tree(comm_dp, p)
        if comm_sp is not None and comm_sp.size > 1:
            p = replicated_tree(comm_sp, p)
        if comm_ep is not None and comm_ep.size > 1:
            p = ep_average_tree(cfg, comm_ep, p)
        loss, stats = _lm_loss(cfg, p, tokens, comm_sp, attn, None, comm_ep,
                               0)
        if comm_dp is not None and comm_dp.size > 1:
            loss = comm_dp.Allreduce(loss, MPI_SUM, compression=False) / comm_dp.size
        if comm_ep is not None and comm_ep.size > 1:
            loss = comm_ep.Allreduce(loss, MPI_SUM, compression=False) / comm_ep.size
        return loss, stats

    (loss, stats), grads = jax.value_and_grad(global_loss,
                                              has_aux=True)(params)
    new_params = jax.tree.map(lambda p, g: p - lr * g, params, grads)
    if not return_stats:
        return loss, new_params
    return loss, new_params, stats or {}
