"""TP-sharded KV-cache decode: the serving compute core.

The training stack shards *gradients* (fuse/zero) — serving shards the
**KV cache**, the HBM-resident state that bounds decode batch size.
Heads are sharded over the communicator following the
:mod:`mpi4torch_tpu.parallel.tp` conventions (each rank owns
``n_heads / size`` query heads and ``kv_heads / size`` KV heads
end-to-end, validated by :func:`parallel.tp.shard_heads`), so per-head
attention never crosses ranks and each layer costs exactly TWO
collectives: the row-parallel output projection's Allreduce and the
row-parallel FFN Allreduce — the Megatron decode schedule.

Three design rules, all serving-specific:

* **per-slot positions** — :func:`decode_step_tp` takes ``pos`` as a
  ``(slots,)`` vector: every slot of the continuous batch sits at its
  own sequence position.  The scalar-``pos`` machinery of
  ``models/transformer.decode_step`` generalizes via
  :func:`~mpi4torch_tpu.ops.ragged.position_onehot` write masks (cache
  update), batched rope rotation, and per-row causal frontiers in the
  attention mask (ops/flash.py) — static shapes throughout, ONE
  compiled step program for any mix of positions.
* **decode comm rides the overlap scheduler** — each per-layer
  Allreduce is issued through
  :func:`~mpi4torch_tpu.overlap.overlap_split_allreduce` (windowed
  split-phase chunk buckets, >= 2 transfers in flight) when the overlap
  policy is on, the blocking facade ``Allreduce`` when off; the
  ``ServeDecode.bucket<i>of<n>`` spans make the schedule censusable by
  :func:`~mpi4torch_tpu.overlap.scheduled_exposure`.
* **latency-tier selection** — decode payloads are ``slots x d_model``
  elements, a few KiB: with ``algorithm=None`` the tune selector keys
  on the real (chunk) message size and lands in the latency tier
  (rhd/tree) below the measured crossover instead of inheriting
  training's bandwidth-tier defaults; the ``select_auto`` latency-tier
  guard keeps aliased bandwidth winners out (ISSUE 10 satellite).

Everything here is **inference-only** (no VJPs — serving never
differentiates) and backend-portable: the same functions run eagerly
inside ``run_ranks`` rank threads (Mode B) and traced under ``run_spmd``
(Mode A), bit-identical under ``deterministic_mode``.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from .. import config as _config
from ..constants import MPI_SUM
from ..models.transformer import GQA, KDA, MLA, Mamba2, \
    TransformerConfig, _MLA_BLOCK, _blockwise_causal_attention, _norm, \
    _split_qkv, branch_norm, dense_ffn, embed_tokens, gqa_out, \
    gqa_project, gqa_scope, index_project, index_select_mask, mamba2_out, \
    mamba2_project, mamba2_scan, mla_expand, mla_project, select_rows, \
    shortcut_branch
from ..ops.flash import flash_attention, flash_block_attention, \
    masked_attention
from ..ops.paged_attention import index_rows_scores, \
    latent_rows_attention, paged_decode_attention, paged_index_scores, \
    paged_latent_attention, paged_sparse_latent_attention
from ..ops.ragged import block_scatter, position_onehot
from ..overlap import overlap_split_allreduce, resolve_overlap
from ..parallel.moe import held_experts_ffn
from ..parallel.tp import shard_axis, shard_heads
from ..runtime import CommError
from ..utils.profiling import bucket_scope, layer_scope, serve_step_scope

__all__ = [
    "validate_tp",
    "shard_params_tp",
    "init_kv_cache_tp",
    "init_kv_pool_tp",
    "install_page_count",
    "install_rows_paged",
    "page_classes",
    "window_of",
    "latent_width",
    "prefill_tp",
    "prefill_chunk_tp",
    "decode_step_tp",
    "decode_step_paged",
    "admit_zero3",
]


# The leaves of a cache entry that are a slot's own and not paged: a
# Mamba-2 layer's state and its convolution's last inputs.
STATE_LEAVES = ("h", "conv")


def has_state(cfg: TransformerConfig) -> bool:
    """Whether a layer of the configuration keeps a per-slot state."""
    return any(isinstance(sp.mixer, Mamba2) for sp in cfg.layer_specs)


def page_classes(cfg: TransformerConfig) -> tuple:
    """The class of pages each layer's cache entry lies in, one a layer:
    ``"window"`` for a :class:`~mpi4torch_tpu.models.transformer.GQA`
    mixer with a window (a slot holds the pages its window touches and
    frees the rest), ``"full"`` for every other layer that keeps rows (a
    slot holds every page up to its frontier), ``None`` for a layer that
    keeps none in pages (an FFN alone, a Mamba-2 state).  Each class has
    its own block-id space, pool extent and block table; a configuration
    with no windowed ``GQA`` layer has the one class ``"full"``, because
    its spec says so."""
    def of(spec):
        if spec.only == "ffn" or isinstance(spec.mixer, Mamba2):
            return None
        windowed = isinstance(spec.mixer, GQA) and spec.mixer.window
        return "window" if windowed else "full"

    return tuple(of(spec) for spec in cfg.layer_specs)


def window_of(cfg: TransformerConfig) -> int:
    """The window of the configuration's window class of pages, 0 where
    it has none; the class has ONE window (:func:`validate_tp`)."""
    return max((sp.mixer.window for sp in cfg.layer_specs
                if isinstance(sp.mixer, GQA)), default=0)


def first_paged_leaf(entries):
    """The first leaf of a cache tree (or of prefill rows) that lies in
    pages: what a page's size or a pass's length is read off."""
    return next(leaf for entry in entries for k, leaf in entry.items()
                if k not in STATE_LEAVES)


def validate_tp(cfg: TransformerConfig, size: int, *,
                prefix_cache: bool = False, prefill_chunk=None) -> None:
    """Serving TP shardability of a model config over ``size`` ranks:
    whole q heads, whole KV heads, and an FFN hidden divisible per rank.
    Uniform top-1 MoE configs (``n_experts > 0``) are refused —
    expert-parallel decode routes through ``parallel/moe.py``'s
    Alltoall, a different serving schedule than the dense TP path this
    subsystem ships.

    A configuration with a per-layer spec is served on ONE rank: latent
    attention (:class:`~mpi4torch_tpu.models.transformer.MLA`) through a
    latent cache entry, a Mamba-2 mixer through a per-slot state entry
    beside the cache, the held share of an expert layer inside the
    compiled prefill and decode step.  Refused by name: a KDA mixer (its
    state entry is not written yet), a router placed before the mixer
    (``LayerSpec.route_on``), and more
    than one rank (the heads of a latent or a state-space layer and the
    experts' exchange are not sharded yet; a shortcut branch is named
    where the spec has one, since it is the branch's exchange that its
    dense path would hide).  With a state layer, ``prefix_cache`` and
    ``prefill_chunk`` (the engine's options) are refused too: a prompt
    that starts from shared pages, or from its own earlier chunk, needs
    the state as it stood at that page's edge, and no snapshot of it is
    kept.  With a WINDOW class of pages (a ``GQA`` mixer with a window,
    :func:`page_classes`) the same two options are refused: a prompt that
    adopts shared pages, or goes on from its own earlier chunk, needs the
    pages under the prefix's last ``window`` positions alive, and a
    window layer has freed them; and the class has one window."""
    if cfg.n_experts > 0:
        raise CommError(
            "serve: MoE configs (n_experts > 0) are not supported by the "
            "dense TP decode path — expert-parallel serving needs the "
            "Alltoall routing schedule")
    if cfg.layers:
        if any(sp.route_on for sp in cfg.layers):
            raise CommError(
                "serve: LayerSpec.route_on (a router that reads the "
                "layer's input, before the mixer) is the training path's: "
                "the serving walk routes on the rows the experts read and "
                "carries no routing across a layer's mixer yet")
        if any(isinstance(sp.mixer, KDA) for sp in cfg.layers):
            raise CommError(
                "serve: a KDA mixer keeps a recurrent state of its own "
                "kind (a matrix a head under a per-channel decay and the "
                "convolutions' inputs of q, k and v), for which the "
                "per-slot state entry is not written yet — latent "
                "attention (MLA), a Mamba2 mixer and the held expert "
                "share are what the serving walk knows of a per-layer "
                "spec")
        state = has_state(cfg)
        if size != 1 and state:
            raise CommError(
                f"serve: a Mamba2 mixer is served on one rank; {size} "
                "ranks would need its heads, their states and the "
                "grouped norm sharded over the ranks, which is not "
                "written yet")
        if state and prefix_cache:
            raise CommError(
                "serve: prefix sharing (ServeConfig.prefix_cache) with a "
                "Mamba2 layer: a prompt that adopts shared pages would "
                "need the recurrent state as it stood at that page's "
                "edge, and no snapshot of it is kept — pass "
                "prefix_cache=False")
        if state and prefill_chunk is not None:
            raise CommError(
                "serve: chunked prefill (ServeConfig.prefill_chunk) with "
                "a Mamba2 layer: the chunk view carries K/V rows between "
                "chunks and no recurrent state yet — prefill in one "
                "piece")
        windows = {sp.mixer.window for sp in cfg.layers
                   if isinstance(sp.mixer, GQA) and sp.mixer.window}
        if len(windows) > 1:
            raise CommError(
                f"serve: GQA layers with windows {sorted(windows)}: the "
                "window class of pages holds ONE window's pages a slot; a "
                "class a window is not written yet")
        if windows and prefix_cache:
            raise CommError(
                "serve: prefix sharing (ServeConfig.prefix_cache) with a "
                "window class of pages (a GQA mixer with a window): a hit "
                "needs the pages under the prefix's last window of "
                "positions alive in every window layer, and those layers "
                "free the pages their window has left — pass "
                "prefix_cache=False")
        if windows and prefill_chunk is not None:
            raise CommError(
                "serve: chunked prefill (ServeConfig.prefill_chunk) with "
                "a window class of pages (a GQA mixer with a window): the "
                "chunk view reads a prompt's earlier chunks back from its "
                "pages, and a window layer holds only the last window of "
                "them — prefill in one piece")
        if size != 1 and any(getattr(sp.mixer, "index", None) is not None
                             for sp in cfg.layers):
            raise CommError(
                f"serve: sparse latent attention (MLA.index) is served on "
                f"one rank; {size} ranks would need the index keys and the "
                "selection shared between the ranks that hold a layer's "
                "heads, which is not written yet")
        if size != 1 and any(sp.shortcut for sp in cfg.layers):
            raise CommError(
                f"serve: a shortcut branch (LayerSpec.branch / .join) is "
                f"served on one rank; {size} ranks would need the "
                "branch's expert exchange overlapped with the mixer and "
                "the dense FFNs it runs beside, which is not written yet")
        if size != 1:
            raise CommError(
                f"serve: a configuration with a per-layer spec is served "
                f"on one rank; {size} ranks would need a latent layer's "
                "heads sharded and the expert layer's exchange, neither "
                "of which is written yet")
        return
    if cfg.n_heads % size != 0 or cfg.kv_heads % size != 0:
        raise CommError(
            f"serve: n_heads={cfg.n_heads} and kv_heads={cfg.kv_heads} "
            f"must both divide into {size} TP ranks (whole-head "
            "sharding)")
    if cfg.d_ff % size != 0:
        raise CommError(
            f"serve: d_ff={cfg.d_ff} not divisible by world size {size}")


def latent_width(spec: MLA) -> int:
    """Channels of a latent cache row: the normed latent and the shared
    key channels, ``kv_rank + qk_rope``, stored up to whole lanes of 128
    (zeros behind them: 512 + 64 -> 640), so that a page is what the
    paged kernel can fetch as it lies."""
    return -(-(spec.kv_rank + spec.qk_rope) // 128) * 128


def _shard_wqkv(cfg: TransformerConfig, comm, wqkv):
    """This rank's column slice of the fused qkv projection — THE one
    place the interleaved q/k/v head-block layout is cut (both
    :func:`shard_params_tp` and :func:`admit_zero3`'s post-pass slice
    through here, so the layout rule cannot drift between them): the
    three head-block ranges each shard by whole heads and re-fuse as
    ``[q_r | k_r | v_r]`` — still one matmul per layer."""
    h, h_kv = cfg.n_heads, cfg.kv_heads
    hd = cfg.d_model // h
    q = wqkv[:, :h * hd]
    k = wqkv[:, h * hd:(h + h_kv) * hd]
    v = wqkv[:, (h + h_kv) * hd:]
    return jnp.concatenate([shard_heads(comm, q, h, 1),
                            shard_heads(comm, k, h_kv, 1),
                            shard_heads(comm, v, h_kv, 1)], axis=1)


def _shard_swiglu_w1(cfg: TransformerConfig, comm, w1):
    """This rank's column slice of the fused swiglu gate|up projection
    (each half sharded separately so the rank keeps MATCHING gate/up
    slices); shared by both shard paths like :func:`_shard_wqkv`."""
    gate, up = w1[:, :cfg.d_ff], w1[:, cfg.d_ff:]
    return jnp.concatenate(
        [shard_axis(comm, gate, 1), shard_axis(comm, up, 1)], axis=1)


def shard_params_tp(cfg: TransformerConfig, params, comm):
    """This rank's tensor-parallel serving shard of a full parameter
    tree (trace-safe: works with a traced SPMD rank).

    Layout (the :mod:`..parallel.tp` column/row pairing per sub-layer):

    * ``wqkv`` — the fused projection splits into its q/k/v head-block
      ranges, each column-sharded by WHOLE heads
      (:func:`parallel.tp.shard_heads`), re-fused as this rank's
      ``[q_r | k_r | v_r]`` slab — one matmul per layer, like the dense
      path;
    * ``wo`` — row-sharded by the same q-head blocks (the row-parallel
      half whose Allreduce is decode collective site 0 of the layer);
    * ``w1`` — column-sharded (swiglu's fused gate|up halves sharded
      separately so each rank keeps matching gate/up slices); ``w2`` —
    * row-sharded (decode collective site 1);
    * embeddings, norms, positional table, unembedding — replicated
      (logits are computed fully on every rank: rank-identical logits
      are what let every rank choose the same tokens at the end of the
      step, ``engine.select_rows``, with no collective).

    At ``size == 1`` every shard is the full matrix — the local serving
    path is the same code with identity collectives."""
    size = comm.size
    validate_tp(cfg, size)
    top = {k: v for k, v in params.items() if k != "blocks"}
    top["blocks"] = [shard_block_tp(cfg, spec, blk, comm) for spec, blk
                     in zip(cfg.layer_specs, params["blocks"])]
    return top


def shard_block_tp(cfg: TransformerConfig, spec, blk, comm):
    """One layer of :func:`shard_params_tp` (the engine shards a layer
    at a time).  A layer a spec names other than the configuration's
    own, or as one part alone, is served on one rank and passes
    whole."""
    if spec.mixer is not None or spec.only:
        return dict(blk)
    out = {"ln1": blk["ln1"], "ln2": blk["ln2"],
           "wqkv": _shard_wqkv(cfg, comm, blk["wqkv"]),
           "wo": shard_heads(comm, blk["wo"], cfg.n_heads, 0)}
    if cfg.ffn == "swiglu":
        out["w1"] = _shard_swiglu_w1(cfg, comm, blk["w1"])
    else:
        out["w1"] = shard_axis(comm, blk["w1"], 1)
    out["w2"] = shard_axis(comm, blk["w2"], 0)
    return out


def init_kv_cache_tp(cfg: TransformerConfig, slots: int, size: int,
                     dtype=jnp.float32):
    """Per-layer TP-sharded slot-table KV cache, zeros:
    ``(slots, max_seq, kv_heads / size, head_dim)`` per rank — the GQA
    saving and the TP saving multiply, which is the whole point of
    sharding the serving cache.  What a whole-prompt prefill
    (:func:`prefill_tp`) writes its rows into, one slot of it, and the
    cache of the reference step :func:`decode_step_tp`; the engine's
    own cache is the paged pool (:func:`init_kv_pool_tp`)."""
    return _cache_entries(
        cfg, (slots, cfg.max_seq), size,
        lambda shape, dt=dtype: jnp.zeros(shape, dt), slots,
        jnp.promote_types(dtype, jnp.float32))


def _cache_entries(cfg: TransformerConfig, lead: tuple, size: int, make,
                   slots: int = 0, state_dtype=jnp.float32,
                   window_lead: tuple = None):
    """One cache entry a layer, each leaf ``make(lead + its row's
    shape)`` (``make(shape, state_dtype)`` for the one leaf that is not
    of the cache's type, a recurrent state): ``{"k", "v"}`` of
    ``(kv_heads / size, head_dim)`` rows for the configuration's own
    attention and of ``(n_kv_heads, head_dim)`` rows, the mixer's own
    counts, for a ``GQA`` mixer (under ``window_lead`` in the place of
    ``lead`` where the mixer has a window and the caller gives one: the
    window class's pool has an extent of its own, :func:`page_classes`),
    ``{"c"}`` of ``(1,
    latent_width)`` rows for a latent layer — one row a token for all
    heads, the normed latent and the rotated shared key
    (:func:`latent_width`: 640 channels, 1,280 bytes in bfloat16, where
    128 heads of keys and values would be 65,536).  A latent layer that
    SCORES (``MLA.index`` an ``Indexer``) has a second leaf beside it,
    ``"ik"`` of ``(1, head_dim)`` rows: the token's index key, what a
    later query's indexer scores this position by (128 channels, 256
    bytes).  Both leaves of a layer live in the same pages: one block
    table, one install, one copy on write.

    Two kinds of layer keep no rows.  A layer that is its FFN alone
    (``LayerSpec.only == "ffn"``) has an EMPTY entry.  A Mamba-2 layer
    keeps what does not grow with the sequence, a SLOT's own and under
    no ``lead``: ``{"h": (slots, n_heads, head_dim, d_state)`` in at
    least float32 whatever the cache's type, ``"conv": (slots, conv - 1,
    conv_dim)}`` in the cache's (:data:`STATE_LEAVES`): the recurrent
    state after the slot's last token and the convolution's last
    inputs.  No table addresses them, no page holds them, and a slot
    that is taken again has them overwritten whole by its prompt's."""
    hd = cfg.d_model // cfg.n_heads
    made = {}                  # one buffer a shape, shared as a template

    def leaf(*row, lead=lead, state=False):
        if (lead, row) not in made:
            made[lead, row] = make(lead + row, state_dtype) if state \
                else make(lead + row)
        return made[lead, row]

    out = []
    for spec in cfg.layer_specs:
        if spec.only == "ffn":
            out.append({})
        elif isinstance(spec.mixer, Mamba2):
            m = spec.mixer
            out.append({
                "h": leaf(m.n_heads, m.head_dim, m.d_state, lead=(slots,),
                          state=True),
                "conv": leaf(m.conv - 1, m.conv_dim, lead=(slots,))})
        elif isinstance(spec.mixer, GQA):
            m = spec.mixer
            kv = leaf(m.n_kv_heads, m.head_dim,
                      lead=window_lead if m.window and window_lead else lead)
            out.append({"k": kv, "v": kv})
        elif isinstance(spec.mixer, MLA):
            out.append({"c": leaf(1, latent_width(spec.mixer))})
            if spec.mixer.scores:
                out[-1]["ik"] = leaf(1, spec.mixer.index.head_dim)
        else:
            kv = leaf(cfg.kv_heads // size, hd)
            out.append({"k": kv, "v": kv})
    return out


def init_kv_pool_tp(cfg: TransformerConfig, num_blocks: int,
                    block_size: int, size: int, dtype=jnp.float32,
                    slots: int = 0, window_blocks: int = 0):
    """Per-layer TP-sharded paged KV pool:
    ``(num_blocks, block_size, kv_heads / size, head_dim)`` per rank
    (a latent layer: one leaf ``(num_blocks, block_size, 1,
    latent_width)``, :func:`_cache_entries`) —
    the paged counterpart of :func:`init_kv_cache_tp`, addressed
    through a per-slot block table instead of a dense per-slot row.
    One block-id space serves every layer of a class (block ``i`` of
    each is the same logical page, so one table drives all their reads);
    the layers of the WINDOW class (:func:`page_classes`) have a pool of
    ``window_blocks`` pages and a table of their own (required where the
    configuration has such a layer).
    A Mamba-2 layer's entry is not paged: its per-slot state and
    convolution inputs are made here, beside the pool, for ``slots``
    slots (:func:`_cache_entries`; required where the configuration has
    such a layer).

    ``block_size`` must divide ``cfg.max_seq``: a slot's table row names
    ``max_seq / block_size`` pages, and where the decode step reads by
    gather (:func:`~mpi4torch_tpu.ops.paged_attention.
    paged_decode_attention`'s jnp path: every backend but a TPU) it
    lays them out as a full ``max_seq`` extent, exactly the dense
    buffer's shape (unmapped pages as inert zero rows behind the causal
    frontier) — that extent equality is part of the bitwise-parity
    contract with the dense path there.

    No poison fill: free state is expressed by table entries (``-1``),
    which read as zero pages on either path — a stale page's bits are
    unreachable without a table entry pointing at it."""
    if block_size < 1 or cfg.max_seq % block_size != 0:
        raise CommError(
            f"serve: block_size={block_size} must be >= 1 and divide "
            f"max_seq={cfg.max_seq} (a slot's table row covers the "
            "dense attention extent)")
    if has_state(cfg) and slots < 1:
        raise CommError(
            "serve: a Mamba2 layer keeps a state a slot beside the pool: "
            "init_kv_pool_tp needs slots >= 1")
    if window_of(cfg) and window_blocks < 1:
        raise CommError(
            "serve: a GQA layer with a window keeps its pages in a class "
            "of their own: init_kv_pool_tp needs window_blocks >= 1")
    return _cache_entries(
        cfg, (num_blocks, block_size), size,
        lambda shape, dt=dtype: jnp.zeros(shape, dt), slots,
        jnp.promote_types(dtype, jnp.float32),
        (window_blocks, block_size))


def install_page_count(n_rows: int, block_size: int) -> int:
    """Pages that ``n_rows`` consecutive positions can touch when the
    first may sit anywhere inside a page: the static page count of
    :func:`install_rows_paged` for rows of that length."""
    return (n_rows + 2 * block_size - 2) // block_size


def install_rows_paged(pool, rows, index, slot=None, classes=None):
    """Write prefill K/V rows into one rank's page pool: the engine
    compiles this once per ``rows`` shape and calls it with ``pool``
    donated, so the pages are written in place.  Where a layer keeps a
    per-slot state (:data:`STATE_LEAVES`), the same call writes the
    prompt's, ``(1, ...)`` in ``rows``, over row ``slot`` (an int32
    scalar) of the pool's: whole, so that nothing of the slot's last
    request is left.

    ``pool`` is :func:`init_kv_pool_tp`'s tree; ``rows`` the same tree
    with ``(1, R, kv_heads/size, head_dim)`` leaves (``(1, R, 1,
    latent_width)`` for a latent layer: the write is the same for every
    kind of entry), row 0 being the first position written.  Everything that changes from install to
    install is in ``index``, an int32 vector ``[off, n, id_0, ...,
    id_{P-1}]`` with ``P = install_page_count(R, block_size)``: rows
    ``0..n-1`` land at offsets ``off, off+1, ...`` of page ``id_0``
    and run on through ``id_1, ...``; rows from ``n`` on are ignored.
    Ids beyond the pages those rows touch must lie outside the pool
    (and differ from each other): the scatter drops them.  Where the
    pool has two classes of pages, ``classes`` names each layer's
    (:func:`page_classes`, static) and ``index`` is ``{class: vector}``:
    a window layer's names the pages its slot holds, the prompt's last
    ones, and ids outside its pool for the pages before them, whose rows
    are dropped likewise, in the one dispatch.

    Exact bits: rows are cast to the pool's dtype and copied; a first
    or last page written in part keeps its other rows (the page is
    read, merged, and written back whole).  The device work is a few
    passes over ``R`` rows per leaf, never over the pool."""
    def leaf(p, r, index):
        off, n, ids = index[0], index[1], index[2:]
        bs = p.shape[1]
        n_pages = install_page_count(r.shape[1], bs)
        if ids.shape[0] != n_pages:
            raise ValueError(
                f"install index names {ids.shape[0]} pages; rows of "
                f"{r.shape[1]} positions in pages of {bs} need {n_pages}")
        # The pool's pages as the paged read views them, (block_size *
        # heads) rows of whole lanes: gathered and scattered in the
        # tiling the pool arrives in.  As (block_size, heads, channels)
        # pages the scatter wanted another tiling of a pool of fewer
        # than 8 heads, and a copy of the whole leaf there and back
        # (2.3 GB of temporaries for a 2.3 GB class of 4 KV heads).
        heads = p.shape[2]
        flat = p.reshape(p.shape[0], bs * heads, p.shape[-1])
        at = jnp.arange(n_pages * bs * heads, dtype=jnp.int32) // heads
        written = ((at >= off) & (at < off + n)).reshape(n_pages, -1, 1)
        new = jax.lax.dynamic_update_slice_in_dim(
            jnp.zeros((n_pages * bs,) + p.shape[2:], p.dtype),
            r[0].astype(p.dtype), off, 0)
        old = jnp.take(flat, ids, axis=0, mode="clip")
        pages = jnp.where(written, new.reshape(old.shape), old)
        return flat.at[ids].set(pages, mode="drop",
                                unique_indices=True).reshape(p.shape)

    def state(p, r):
        return jax.lax.dynamic_update_index_in_dim(
            p, r[0].astype(p.dtype), slot, 0)

    of = (lambda layer: index) if classes is None \
        else (lambda layer: index[classes[layer]])
    return [{k: state(p[k], r[k]) if k in STATE_LEAVES
             else leaf(p[k], r[k], of(layer)) for k in sorted(p)}
            for layer, (p, r) in enumerate(zip(pool, rows))]


def _tp_size(cfg: TransformerConfig, shards) -> int:
    """The TP world size a shard tree was built for, read off the
    output projection's row count (``h_local * head_dim``) — so the
    compute functions need no communicator to agree with their
    shards."""
    if cfg.layers:
        return 1                       # validate_tp: one rank
    hd = cfg.d_model // cfg.n_heads
    h_local = shards["blocks"][0]["wo"].shape[0] // hd
    return cfg.n_heads // h_local


def _decode_allreduce(comm, x, *, site: int, nsites: int, overlap,
                      algorithm=None):
    """One decode collective site: the row-parallel partial-sum
    Allreduce, scheduled per the overlap policy.  ``overlap`` truthy →
    the windowed split-phase chunk window
    (:func:`~mpi4torch_tpu.overlap.overlap_split_allreduce`, bucket
    labels globally numbered over the step's ``nsites`` sites); falsy →
    the blocking facade op under a plain (exposed-by-construction)
    bucket span, the censusable baseline.  Always exact
    (``compression=False`` — decode activations are forward values, the
    house rule that keeps a gradient-codec scope off them)."""
    if comm is None:
        return x
    if overlap:
        k = _config.serve_decode_buckets()
        return overlap_split_allreduce(
            comm, x, MPI_SUM, nsplits=k, index_base=site * k,
            index_total=nsites * k, op_name="ServeDecode",
            algorithm=algorithm)
    with bucket_scope("ServeDecode", site, nsites):
        return comm.Allreduce(x, MPI_SUM, compression=False,
                              algorithm=algorithm)


class _Latent:
    """One latent layer's attention, both ways over one cache.  A cache
    row is ``[c ; k_r ; 0]`` (:func:`latent_width`): the normed latent
    and the rotated key channels all heads share.

    * :meth:`expanded` (the prefills): the rows go up through ``wb`` to
      every head's keys and values and the flash path attends at
      query-key size ``qk_nope + qk_rope`` with the value padded, as the
      training forward does;
    * :meth:`absorbed` + :meth:`values` (the decode steps): with ``wb =
      [Wuk ; Wuv]`` a head, ``q_n . (c Wuk) = (q_n Wuk^T) . c``, so the
      query goes DOWN to the latent instead (``[q_n Wuk^T ; q_r ; 0]``),
      every head scores the cached row itself, the weighted sum is taken
      over the rows' first ``kv_rank`` channels and only that sum goes
      up through ``Wuv``: keys and values are never formed, and a row
      is read once for all heads.

    The same mathematics (``tests/test_openpangu_moe.py`` holds the two
    together on one cache)."""

    def __init__(self, spec: MLA, p):
        self.spec, self.p = spec, p
        self.width = latent_width(spec)
        self.scale = float(spec.qk_nope + spec.qk_rope) ** -0.5

    def rows(self, c, k_r):
        """``(b, s, 1, width)`` cache rows of a pass's latents."""
        pad = self.width - c.shape[-1] - k_r.shape[-1]
        return jnp.concatenate(
            [c, k_r, jnp.zeros(c.shape[:-1] + (pad,), c.dtype)],
            axis=-1)[:, :, None, :]

    def expanded(self, q, rows, q_offset=None, selected=None):
        """``q`` ``(b, s, h, qk)`` over ``rows`` ``(b, n, 1, width)``:
        the whole sequence causally (``q_offset`` None: ``n == s``), or
        from global offset ``q_offset`` over ``rows`` from 0 on (a chunk
        against cached rows; the jnp path, as the K/V chunk view).
        ``selected`` ``(s, n)`` bool, of an indexed layer: each query
        over the rows its own row of the mask names and no others
        (``ops.flash.masked_attention``, one sequence)."""
        sp = self.spec
        rows = rows[:, :, 0].astype(q.dtype)
        if selected is not None:
            return self._expanded_selected(q, rows, q_offset or 0, selected)
        k, v = mla_expand(sp, self.p, rows[..., :sp.kv_rank],
                          rows[..., sp.kv_rank:sp.kv_rank + sp.qk_rope])
        if q_offset is None:
            o = _blockwise_causal_attention(q, k, v, _MLA_BLOCK)
        else:
            o, _ = flash_block_attention(
                q, k, v, causal=True, q_offset=q_offset, kv_offset=0,
                impl="jnp")
        return o[..., :sp.v_dim]

    def _expanded_selected(self, q, rows, q_offset, selected):
        """:meth:`expanded` under a selection, a group of heads at a
        time, one after the other: the keys and values of 16 heads of a
        16,384-token prompt are 0.27 GB where all 64 heads' are 1.1 GB
        beside the 0.94 GB product they are cut from, and the one-piece
        prefill has to fit beside the weights and the pool."""
        sp = self.spec
        c = rows[..., :sp.kv_rank]
        k_r = rows[..., sp.kv_rank:sp.kv_rank + sp.qk_rope]
        group = next(g for g in (16, 8, 4, 2, 1) if sp.n_heads % g == 0)
        n = sp.n_heads // group
        part = dataclasses.replace(sp, n_heads=group)

        def heads(args):
            q_g, wb_g = args
            k, v = mla_expand(part, {"wb": wb_g}, c, k_r)
            return masked_attention(q_g, k, v, selected, q_offset=q_offset)

        b, s, _, d = q.shape
        o = jax.lax.map(heads, (
            jnp.moveaxis(q.reshape(b, s, n, group, d), 2, 0),
            jnp.moveaxis(self.p["wb"].reshape(sp.kv_rank, n, -1), 1, 0)))
        return jnp.moveaxis(o, 0, 2).reshape(b, s, sp.n_heads, -1)[
            ..., :sp.v_dim]

    def _up(self):
        sp = self.spec
        return self.p["wb"].reshape(sp.kv_rank, sp.n_heads,
                                    sp.qk_nope + sp.v_dim)

    def absorbed(self, q):
        """``q`` ``(slots, h, qk)`` -> ``(slots, h, width)``."""
        sp = self.spec
        qc = jnp.einsum("shn,rhn->shr", q[..., :sp.qk_nope],
                        self._up()[..., :sp.qk_nope])
        pad = self.width - sp.kv_rank - sp.qk_rope
        return jnp.concatenate(
            [qc, q[..., sp.qk_nope:],
             jnp.zeros(q.shape[:-1] + (pad,), q.dtype)], axis=-1)

    def values(self, u):
        """``u`` ``(slots, h, kv_rank)`` -> ``(slots, h, v_dim)``."""
        return jnp.einsum("shr,rhv->shv", u,
                          self._up()[..., self.spec.qk_nope:])


def _walk_layers(cfg: TransformerConfig, shards, x, positions, attend,
                 attend_latent, reduce, live=None, select=None, scan=None):
    """The serving transformer block, once, for every serving program:
    per layer ``ln1`` → mixer → ``reduce`` → residual → ``ln2`` → FFN →
    ``reduce`` → residual (a layer of one part, ``LayerSpec.only``: its
    own norm, part, ``reduce`` and residual, and nothing of the other);
    then ``ln_f``.  Returns ``(x, entries,
    counts)``: the normed hidden rows, what the view handed back for
    each layer, and what the expert layers counted (``{}`` without
    one): ``moe_rows``, the rows each held expert took in every expert
    layer, ``(expert layers, held)`` (``(pieces, expert layers, held)``
    from a prompt long enough for its expert layers to run in pieces,
    :func:`_held_experts_in_pieces`), ``moe_overflow_calls``, how many
    calls of an expert layer (a piece is one) had held rows behind
    their prefix (``parallel.moe.held_experts_ffn``), and, where a layer has
    zero-compute experts, ``moe_zero_pairs`` and ``moe_live_pairs``, the
    live (token, choice) pairs that chose one and all of them, summed
    over those layers; and from a DECODE step with indexed layers
    ``dsa_rows_live``, the latent rows under the live slots' frontiers
    summed over the indexed layers, ``dsa_rows_read``, the rows their
    selections name (what attention reads of them), and
    ``dsa_rows_scored``, the index keys the scoring layers scored; and
    from a decode step with Mamba-2 layers ``ssm_states_live``, the
    (live slot, layer) pairs whose kept state the step had to advance,
    and ``ssm_states_touched``, those it read and wrote (every slot's:
    the update runs over the whole slot table).

    ``x`` is the embedded input, ``(b, s, d)`` with ``positions`` ``(s,)``
    (a prefill) or ``(slots, d)`` with one position a slot (a decode
    step, split as sequences of one).  What differs between the four
    programs is in the callables:

    * ``attend(layer, q, k, v, window) -> (o, entry)`` is the cache VIEW
      of a layer of softmax attention over keys and values, the
      configuration's own (``window`` is ``cfg.attn_window``) or a
      ``GQA`` mixer's (its own): it stores this pass's
      K/V rows of layer ``layer`` its own way, attends ``q`` over what
      that layer may see, and returns the attention output (any shape
      that flattens to ``x``'s rows) and the layer's new cache entry
      (or the rows to install);
    * ``attend_latent(layer, q, rows, lat, selected) -> (o, entry)`` is
      the same view of a latent layer: ``rows`` are this pass's cache
      rows (``lat.rows``), ``lat`` the layer's :class:`_Latent`, whose
      ``expanded`` a prefill calls and whose ``absorbed`` / ``values`` a
      decode step calls; ``o`` holds every head's ``v_dim`` channels;
      ``selected`` is ``None``, or on an indexed layer (``MLA.index``)
      the selection the layer attends and nothing beside it;
    * ``select(layer, q_i, k_i, w, ix) -> (selected, entry)`` is the view
      of a SCORING layer's index keys: it stores this pass's keys ``k_i``
      ``(b, s, 1, head_dim)`` its own way, scores what the layer may see
      with the index queries ``q_i`` and weights ``w``
      (``transformer.index_project``) and hands back the selection in
      the form its ``attend_latent`` takes (a prefill: a ``(s, n)``
      mask; a decode step: ``(slots, top_k)`` positions) and the index
      keys' new cache entry.  The selection is CARRIED along the walk:
      the layers above with ``MLA.index="shared"`` attend it as it is,
      until the next scoring layer replaces it;
    * ``scan(layer, spec, p, xBC, dt) -> (y, entry)`` is the view of a
      Mamba-2 layer's kept state: it runs the convolution and the
      recurrence (``transformer.mamba2_scan``) from where the layer's
      sequences stand — a prefill from nothing, over the whole prompt,
      under ``layer_scope("ssm_scan")``; a decode step from each slot's
      entry, one token on, under ``layer_scope("ssm_update")`` — and
      hands back the recurrence's output and the entry as the pass
      leaves it (a prefill's: what the install writes over the slot's);
    * ``reduce(partial, site, nsites)`` sums a row-parallel partial
      product over the TP ranks; the sites are counted here, two a layer.

    A layer is what its :class:`~mpi4torch_tpu.models.transformer.
    LayerSpec` names (``cfg.layer_specs``; without a spec every layer is
    the configuration's own): the mixer the configuration's attention, a
    ``GQA`` mixer whole under ``layer_scope("attn")`` (``gqa_project``,
    the view's ``attend`` under ``attn_window`` or ``attn_full``,
    ``gqa_out``), a
    Mamba-2 mixer whole under ``layer_scope("ssm")`` (``mamba2_project``,
    the view's ``scan``, ``mamba2_out``) or
    latent attention under ``layer_scope("mla")``, through the
    projections the training forward uses (``mla_project``), a scoring
    layer's indexer (projections, cache write, scoring and top-k) under
    ``layer_scope("dsa")`` beside it; the FFN
    dense or the held share of an expert layer under
    ``layer_scope("moe")`` (``live`` ``(rows,)`` keeps a decode step's
    free slots out of the groups and the counts); a second norm on each
    branch where the spec states one; a shortcut branch
    (``LayerSpec.branch``) computed on its layer's ``ln2`` rows, carried
    across the layers it runs beside and added behind the FFN residual
    of the layer that joins it, with the dense FFN of those layers under
    ``layer_scope("ffn")`` (``shortcut_branch``, ``dense_ffn``: the
    training forward's helpers).  This is the one place on the
    serving path that knows a kind of layer; what it does not know
    ``validate_tp`` refuses by name."""
    size = _tp_size(cfg, shards)
    validate_tp(cfg, size)
    nsites = 2 * len(shards["blocks"])
    # The projections take sequences: a decode step's rows are of one.
    seq = (lambda a: a[:, None]) if x.ndim == 2 else (lambda a: a)
    entries, moe_rows, zero_pairs, live_pairs, overflows = [], [], [], [], []
    carried = selected = None
    dsa, ssm = {}, {}

    def dsa_counted(spec, selected):
        # A decode step's rows: held under the frontiers, named by the
        # selection (free slots name none), scored by this layer.
        held = jnp.sum(positions + 1 if live is None
                       else jnp.where(live, positions + 1, 0),
                       dtype=jnp.int32)
        for name, rows in (
                ("dsa_rows_live", held),
                ("dsa_rows_read", jnp.sum(selected >= 0, dtype=jnp.int32)),
                ("dsa_rows_scored", held if spec.scores else 0)):
            dsa[name] = dsa.get(name, 0) + rows

    def counted(spec, taken, zero, overflow):
        moe_rows.append(taken)
        overflows.append(overflow)
        if spec.n_zero:
            zero_pairs.append(zero)
            live_pairs.append(spec.top_k * (
                x.size // x.shape[-1] if live is None
                else jnp.sum(live, dtype=jnp.int32)))

    def ffn_part(spec, blk, y):
        # The FFN of a layer on its ln2 rows ``y``, before the reduction
        # and the residual.
        if spec.ffn is None:
            return branch_norm(cfg, spec, blk,
                               dense_ffn(cfg, spec, blk, y), "ln2_post")
        with layer_scope("moe"):
            ff, *counts = _held_experts_in_pieces(
                y.reshape(-1, y.shape[-1]), blk["experts"], spec.ffn,
                live)
            ff = branch_norm(cfg, spec, blk, ff.reshape(y.shape),
                             "ln2_post")
        counted(spec.ffn, *counts)
        return ff

    for layer, (spec, blk) in enumerate(zip(cfg.layer_specs,
                                            shards["blocks"])):
        if spec.only == "ffn":
            entries.append({})
            x = x + reduce(ffn_part(spec, blk, _norm(cfg, x, blk["ln2"])),
                           2 * layer + 1, nsites).astype(x.dtype)
            continue
        y = _norm(cfg, x, blk["ln1"])
        if spec.mixer is None:
            q, k, v = _split_qkv(cfg, blk, seq(y), seq(positions), size)
            o, entry = attend(layer, q, k, v, cfg.attn_window)
            o_part = o.reshape(*x.shape[:-1], -1).astype(x.dtype) \
                @ blk["wo"]
        elif isinstance(spec.mixer, GQA):
            with layer_scope("attn"):
                m = spec.mixer
                q, k, v, g = gqa_project(m, blk["mixer"], seq(y),
                                         seq(positions))
                with gqa_scope(m):
                    o, entry = attend(layer, q, k, v, m.window)
                o = o.reshape(*x.shape[:-1], m.n_heads, m.head_dim)
                o_part = branch_norm(
                    cfg, spec, blk,
                    gqa_out(m, blk["mixer"], o.astype(x.dtype), g),
                    "ln1_post")
        elif isinstance(spec.mixer, Mamba2):
            with layer_scope("ssm"):
                z, xBC, dt = mamba2_project(spec.mixer, blk["mixer"], seq(y))
                o, entry = scan(layer, spec.mixer, blk["mixer"], xBC, dt)
                o_part = mamba2_out(spec.mixer, blk["mixer"], o,
                                    z).reshape(x.shape)
            if x.ndim == 2:
                slots = x.shape[0]
                ssm["ssm_states_live"] = ssm.get("ssm_states_live", 0) + (
                    slots if live is None
                    else jnp.sum(live, dtype=jnp.int32))
                ssm["ssm_states_touched"] = ssm.get(
                    "ssm_states_touched", 0) + slots
        else:
            with layer_scope("mla"):
                lat = _Latent(spec.mixer, blk["mixer"])
                q, c, k_r, cq = mla_project(cfg, spec.mixer, blk["mixer"],
                                            seq(y), seq(positions))
            scored = {}
            if spec.mixer.scores:
                with layer_scope("dsa"):
                    ix = spec.mixer.index
                    q_i, k_i, w = index_project(
                        cfg, ix, blk["mixer"]["index"], seq(y), cq,
                        seq(positions))
                    selected, scored["ik"] = select(
                        layer, q_i, k_i[:, :, None, :], w, ix)
            indexed = spec.mixer.index is not None
            with layer_scope("mla"):
                o, entry = attend_latent(layer, q, lat.rows(c, k_r), lat,
                                         selected if indexed else None)
                entry = {**entry, **scored}
                o_part = branch_norm(
                    cfg, spec, blk,
                    o.reshape(*x.shape[:-1], -1).astype(x.dtype)
                    @ blk["mixer"]["wo"], "ln1_post")
            if indexed and x.ndim == 2:
                dsa_counted(spec.mixer, selected)
        entries.append(entry)
        x = x + reduce(o_part, 2 * layer, nsites).astype(x.dtype)
        if spec.only == "mixer":
            continue
        y = _norm(cfg, x, blk["ln2"])
        if spec.branch is not None:
            carried, *counts = shortcut_branch(spec, blk, y, live=live)
            counted(spec.branch, *counts)
        x = x + reduce(ffn_part(spec, blk, y), 2 * layer + 1,
                       nsites).astype(x.dtype)
        if spec.join:
            x, carried = x + carried.astype(x.dtype), None
    x, counts = _norm(cfg, x, shards["ln_f"]), {
        k: jnp.asarray(v, jnp.int32) for k, v in {**dsa, **ssm}.items()}
    if moe_rows:
        # (expert layers, held); of a prompt whose expert layers ran in
        # pieces, (pieces, expert layers, held).
        counts["moe_rows"] = jnp.moveaxis(jnp.stack(moe_rows), 0, -2)
        counts["moe_overflow_calls"] = sum(overflows)
    if zero_pairs:
        counts["moe_zero_pairs"] = sum(zero_pairs)
        counts["moe_live_pairs"] = jnp.asarray(sum(live_pairs), jnp.int32)
    return x, entries, counts


# Rows of one call of the expert layer: the layer keeps a buffer of
# every (token, choice) pair at the stream's width and again at the
# experts', 1.5 GB in all for 4,096 tokens choosing 8 of 6,144 channels.
_EXPERT_ROWS = 4096


def _held_experts_in_pieces(x, params, spec, live):
    """``held_experts_ffn`` on ``x`` ``(T, d)``, ``_EXPERT_ROWS`` tokens
    at a time where there are more (a long prompt's one-piece prefill):
    routing is a token's own affair, so the pieces' outputs are the
    whole's, and the layer's buffers are a piece's.  The rows the held
    experts took come back a piece at a time then, ``(pieces, held)``:
    each piece is a call of the grouped products with group sizes of its
    own, and is counted as one; the other two counts are summed."""
    T, d = x.shape
    if T <= _EXPERT_ROWS:
        return held_experts_ffn(x, params, spec, live=live)
    # Written out piece by piece: as the operands of a loop the experts'
    # matrices would be copied into its state, 1.2 GB a layer.
    pieces = [held_experts_ffn(
        x[at:at + _EXPERT_ROWS], params, spec,
        live=None if live is None else live[at:at + _EXPERT_ROWS])
        for at in range(0, T, _EXPERT_ROWS)]
    y, taken, zero, overflow = zip(*pieces)
    return jnp.concatenate(y), jnp.stack(taken), sum(zero), sum(overflow)


def _state_view(cache, scope: str, carried: bool):
    """The ``scan`` callable of a view over the Mamba-2 layers' entries
    of ``cache``, under ``layer_scope(scope)``: from each sequence's
    kept entry where ``carried`` (a decode step: one token on), from
    nothing otherwise (a prefill: the whole prompt).  The entry handed
    back has the cache's types."""
    def scan(layer, spec, p, xBC, dt):
        kept = cache[layer]
        with layer_scope(scope):
            y, entry = mamba2_scan(spec, p, xBC, dt,
                                   kept if carried else None)
        return y, {k: entry[k].astype(kept[k].dtype) for k in kept}

    return scan


def _hand_out(stats, counts):
    """A serving program's counters (:func:`_walk_layers`' ``counts``),
    into the caller's ``stats`` dict (the one route out: the engine's
    step record takes them from there)."""
    if stats is not None:
        stats.update(counts)


def _prefill_reduce(comm):
    """The prefills' reduction: one blocking Allreduce per row-parallel
    half (prefill is the compute-bound phase, so its collectives stay
    out of the decode exposure census)."""
    def reduce(partial, site, nsites):
        if comm is None:
            return partial
        return comm.Allreduce(partial, MPI_SUM, compression=False)

    return reduce


def _live_rows(active):
    """A decode step's ``active`` argument as a ``(slots,)`` bool mask."""
    return None if active is None else jnp.asarray(active).astype(bool)


def _frontier(pos, extent: int, live):
    """``(slots, extent)`` bool: the positions a decode step's slot may
    score, ``0..pos``, none for a free slot."""
    seen = jnp.arange(extent, dtype=jnp.int32)[None, :] <= pos[:, None]
    return seen if live is None else seen & live[:, None]


def _decode_reduce(comm, live, overlap, algorithm):
    """The decode steps' reduction: free slots' rows zeroed (a poisoned
    row never reaches the wire; ``where`` selects, so live rows pass
    bit for bit), then :func:`_decode_allreduce` at the walker's
    site."""
    ov = resolve_overlap(overlap)
    if live is not None:
        live = live[:, None]

    def reduce(partial, site, nsites):
        if live is not None:
            partial = jnp.where(live, partial,
                                jnp.zeros((), partial.dtype))
        return _decode_allreduce(comm, partial, site=site, nsites=nsites,
                                 overlap=ov, algorithm=algorithm)

    return reduce


def prefill_tp(cfg: TransformerConfig, shards, cache, prompt, comm=None,
               stats=None):
    """TP prefill: populate this rank's KV-cache shard rows from a whole
    prompt in one batched pass and return ``(last_logits, new_cache)``
    — the serving mirror of ``models/transformer.prefill`` (same op
    sequence per rank; one blocking Allreduce per row-parallel half —
    prefill is the compute-bound phase, so its collectives stay on the
    blocking path and out of the decode exposure census).  ``stats``, a
    dict, receives the program's counters (``moe_rows`` where the
    configuration has an expert layer)."""
    p_len = prompt.shape[1]
    x = embed_tokens(cfg, shards, prompt)
    if cfg.pos_table:
        x = x + shards["pos"][None, :p_len]
    positions = jnp.arange(p_len, dtype=jnp.int32)

    def attend(layer, q, k, v, window):
        # Rows written at 0; attention over this pass's own K/V, so a
        # lower-precision cache does not touch the prompt's logits.
        c = cache[layer]
        ck = jax.lax.dynamic_update_slice_in_dim(
            c["k"], k.astype(c["k"].dtype), 0, 1)
        cv = jax.lax.dynamic_update_slice_in_dim(
            c["v"], v.astype(c["v"].dtype), 0, 1)
        o = flash_attention(q, k, v, causal=True, window=window)
        return o, {"k": ck, "v": cv}

    def attend_latent(layer, q, rows, lat, selected):
        # The same for a latent layer: rows written at 0, attention over
        # this pass's own rows, expanded; under a selection, each query
        # over the rows it names.
        c = cache[layer]["c"]
        cc = jax.lax.dynamic_update_slice_in_dim(
            c, rows.astype(c.dtype), 0, 1)
        return lat.expanded(q, rows, selected=selected), {"c": cc}

    def select(layer, q_i, k_i, w, ix):
        # Index keys written at 0; every query scores this pass's own
        # keys up to its position.
        c = cache[layer]["ik"]
        return (index_select_mask(q_i[0], k_i[0, :, 0], w[0], ix.top_k),
                jax.lax.dynamic_update_slice_in_dim(
                    c, k_i.astype(c.dtype), 0, 1))

    with serve_step_scope("prefill"):
        x, new_cache, counts = _walk_layers(
            cfg, shards, x, positions, attend, attend_latent,
            _prefill_reduce(comm), select=select,
            scan=_state_view(cache, "ssm_scan", carried=False))
        _hand_out(stats, counts)
        return x[:, -1] @ shards["unembed"], new_cache


def prefill_chunk_tp(cfg: TransformerConfig, shards, past, chunk,
                     comm=None, stats=None):
    """TP prefill of one prompt CHUNK against already-computed prefix
    K/V: the suffix/chunked half of paged admission.  ``chunk`` is
    ``(1, c_len)`` tokens occupying global positions ``p_len ..
    p_len + c_len - 1`` where ``p_len`` is read off ``past`` — a
    per-layer ``[{"k", "v"}]`` list of EXACT-length ``(1, p_len, ...)``
    prefix rows (``p_len = 0`` arrays make this a from-scratch prefill
    of the same math as :func:`prefill_tp`).  Returns ``(last_logits,
    chunk_rows)`` with ``chunk_rows`` the chunk's own K/V in ``past``'s
    dtype, ready to install into the page pool.

    Bitwise contract: the chunk's rows attend ``[past ++ chunk]``
    through the same jnp attention path as the full prefill with the
    matching global ``q_offset``, so row ``i`` of a chunked prefill
    carries the bits row ``i`` of the one-shot prefill would — prompt
    rows depend only on the tokens at or before them (causal masking),
    which is the fact prefix SHARING rides: a prefix prefilled under
    one request is bit-valid for every request extending it.  Exactness
    requires ``past`` to carry the compute dtype (the engine gates
    prefix sharing and chunking on ``cache_dtype == param dtype``; a
    down-cast cache would re-quantize the prefix rows the one-shot
    oracle keeps at full precision).

    Collectives are the blocking prefill path (compute-bound phase,
    outside the decode exposure census), one per row-parallel half."""
    validate_tp(cfg, _tp_size(cfg, shards), prefill_chunk=chunk.shape[1])
    c_len = chunk.shape[1]
    p_len = int(jax.tree.leaves(past[0])[0].shape[1])
    x = embed_tokens(cfg, shards, chunk)
    if cfg.pos_table:
        x = x + shards["pos"][None, p_len:p_len + c_len]
    positions = jnp.arange(p_len, p_len + c_len, dtype=jnp.int32)

    def attend(layer, q, k, v, window):
        # The chunk's rows go back to be installed; they attend
        # past ++ chunk from their global offset.
        p = past[layer]
        rows = {"k": k.astype(p["k"].dtype), "v": v.astype(p["v"].dtype)}
        kf = jnp.concatenate([p["k"].astype(k.dtype), k], axis=1)
        vf = jnp.concatenate([p["v"].astype(v.dtype), v], axis=1)
        o, _ = flash_block_attention(
            q, kf, vf, causal=True, q_offset=p_len, kv_offset=0,
            window=window, impl="jnp")
        return o, rows

    def attend_latent(layer, q, rows, lat, selected):
        # A latent layer's chunk: its rows go back to be installed and
        # attend past ++ chunk, expanded, from their global offset.
        p = past[layer]["c"]
        full = jnp.concatenate([p.astype(rows.dtype), rows], axis=1)
        return (lat.expanded(q, full, q_offset=p_len, selected=selected),
                {"c": rows.astype(p.dtype)})

    def select(layer, q_i, k_i, w, ix):
        # The chunk's index keys go back to be installed; its queries
        # score past ++ chunk from their global offset.
        p = past[layer]["ik"]
        full = jnp.concatenate([p.astype(k_i.dtype), k_i], axis=1)
        return (index_select_mask(q_i[0], full[0, :, 0], w[0], ix.top_k,
                                  q_offset=p_len), k_i.astype(p.dtype))

    with serve_step_scope("prefill"):
        x, rows, counts = _walk_layers(
            cfg, shards, x, positions, attend, attend_latent,
            _prefill_reduce(comm), select=select)
        _hand_out(stats, counts)
        return x[:, -1] @ shards["unembed"], rows


def decode_step_tp(cfg: TransformerConfig, shards, cache, tokens, pos,
                   comm=None, *, overlap=None,
                   algorithm: Optional[str] = None, active=None,
                   stats=None):
    """One continuous-batching decode step over the whole slot table:
    logits for ``tokens`` ``(slots,)``, each slot at its OWN position
    ``pos[slot]`` ``(slots,)``, updating this rank's KV-cache shard.
    Returns ``(logits (slots, vocab), new_cache)``.

    **The reference step.**  The engine does not call this: it steps
    through :func:`decode_step_paged` for every ``ServeConfig``.  This
    is the step the paged view is held against — the same walk over a
    dense ``(slots, max_seq)`` cache (:func:`init_kv_cache_tp`) with no
    table, no scatter and no kernel — and the one oracle of a served
    layer spec inside the package; a new mixer or cache entry writes
    its view here too, so that the tests compare two independent reads.

    Per slot this is exactly ``models/transformer.decode_step``'s math
    (teacher-forcing equivalent to the training forward), vectorized
    over per-slot positions: the cache write is a
    :func:`~mpi4torch_tpu.ops.ragged.position_onehot` masked ``where``
    (same written bits as the scalar ``dynamic_update_slice``), rope
    rotates with per-row angles, and attention masks per-row causal /
    sliding-window frontiers over the full static ``max_seq`` buffer —
    no length bookkeeping, no retrace as traffic churns.  Free slots
    (whatever ``pos``/``tokens`` they carry) compute row-local garbage
    that never touches live rows: every op is row-wise and the TP
    collectives reduce over RANKS, not slots.

    ``overlap``: ``None`` defers to ``config.default_overlap()``;
    truthy rides each of the ``2 * n_layers`` collective sites through
    the windowed split-phase chunk window (``scheduled_exposure``
    strictly < 1.0); ``False`` pins the blocking baseline (censuses
    1.0).  ``algorithm=None`` lets the tune selector key on the real
    chunk sizes — the latency tier for per-token traffic.

    ``active`` (``(slots,)`` bool/int, optional) zeroes the FREE slots'
    rows of every collective payload before it touches the wire: a
    free slot's garbage (NaN where a caller poisons its rows) otherwise
    rides the allreduce and trips PR 7's finite guard
    (``config.comm_finite_guard``) with a false corruption attribution
    on healthy ranks.  Live rows pass through the mask bit-identically
    (``where`` selects, never scales), so the parity contract is
    untouched.

    Inference-only: no VJP (serving never differentiates), and the
    sliding-window case attends the full buffer with the window mask
    (the position-tracking bucket slice of ``decode_step`` is a
    single-sequence optimization; per-slot gathers would re-shuffle the
    cache every step for a smoke-scale win)."""
    pos = jnp.asarray(pos, jnp.int32)
    live = _live_rows(active)
    reduce = _decode_reduce(comm, live, overlap, algorithm)

    def attend(layer, q, k, v, window):
        # One-hot ``where`` write of each slot's row; attention over
        # the whole max_seq buffer behind per-row frontiers.
        c = cache[layer]
        wmask = (position_onehot(pos, cfg.max_seq) != 0)[:, :, None, None]
        ck = jnp.where(wmask, k.astype(c["k"].dtype), c["k"])
        cv = jnp.where(wmask, v.astype(c["v"].dtype), c["v"])
        o, _ = flash_block_attention(
            q, ck, cv, causal=True, q_offset=pos, kv_offset=0,
            window=window, impl="jnp")
        return o, {"k": ck, "v": cv}

    def attend_latent(layer, q, rows, lat, selected):
        # The same write of a latent row; the read is absorbed: every
        # head scores the slot's rows as they lie, or, under a
        # selection, the rows it names, taken out of the slot's extent.
        c = cache[layer]["c"]
        wmask = (position_onehot(pos, cfg.max_seq) != 0)[:, :, None, None]
        cc = jnp.where(wmask, rows.astype(c.dtype), c)
        seen, upto = cc[:, :, 0], pos
        if selected is not None:
            seen = jnp.take_along_axis(
                seen, jnp.maximum(selected, 0)[:, :, None], axis=1)
            upto = jnp.sum(selected >= 0, axis=-1, dtype=jnp.int32) - 1
        u = latent_rows_attention(
            lat.absorbed(q[:, 0]), seen, upto,
            v_width=lat.spec.kv_rank, scale=lat.scale)
        return lat.values(u), {"c": cc}

    def select(layer, q_i, k_i, w, ix):
        # The same write of an index key; every slot scores its extent
        # and keeps the top_k positions up to its frontier.
        c = cache[layer]["ik"]
        wmask = (position_onehot(pos, cfg.max_seq) != 0)[:, :, None, None]
        cc = jnp.where(wmask, k_i.astype(c.dtype), c)
        scores = index_rows_scores(q_i[:, 0], cc[:, :, 0], w[:, 0])
        return select_rows(scores, _frontier(pos, cfg.max_seq, live),
                           ix.top_k), cc

    with serve_step_scope("decode_step"):
        x = embed_tokens(cfg, shards, tokens)
        if cfg.pos_table:
            x = x + jnp.take(shards["pos"], pos, axis=0)
        x, new_cache, counts = _walk_layers(
            cfg, shards, x, pos, attend, attend_latent, reduce, live,
            select, _state_view(cache, "ssm_update", carried=True))
        _hand_out(stats, counts)
        return x @ shards["unembed"], new_cache


# The eager engine's write: one jitted scatter per pool leaf with the
# leaf donated, so that off ``run_spmd`` too the row lands in the pool's
# own buffer instead of in a copy of it.
_block_scatter_donated = jax.jit(block_scatter, donate_argnums=0)


def decode_step_paged(cfg: TransformerConfig, shards, pool, table,
                      tokens, pos, comm=None, *, overlap=None,
                      algorithm: Optional[str] = None, active=None,
                      donate: bool = False, stats=None):
    """One continuous-batching decode step over a PAGED slot table:
    :func:`decode_step_tp`'s math with the dense per-slot cache
    replaced by ``pool`` (per-layer ``(num_blocks, block_size,
    kv_heads/size, head_dim)`` pages, :func:`init_kv_pool_tp`) plus a
    ``(slots, max_seq/block_size)`` block ``table`` (``-1`` =
    unmapped).  Returns ``(logits, new_pool)``.  Where the pool has two
    classes of pages (:func:`page_classes`), ``table`` is ``{"full": ...,
    "window": ...}``, a table a class: a layer writes and reads through
    its class's, a window layer under its window, behind which its
    table's entries are ``-1`` and its pages someone else's.

    The step reaches the pool only through the table, in both
    directions, and forms no array of the pool's size.  Per layer:

    * the new K/V row of each live slot lands by
      :func:`~mpi4torch_tpu.ops.ragged.block_scatter` — a scatter of
      ``slots`` rows — in the slot's current page (``table[s,
      pos[s]//bs]`` at offset ``pos[s] % bs``): exact bits on every
      backend, and written into the pool's own buffers when the pool
      is donated.  A compiled caller donates through its own jit (the
      SPMD engine: ``run_spmd(..., donate_argnums=...)``); an eager
      caller passes ``donate=True``, and each leaf then goes through a
      jitted scatter that takes it over.  Either way the caller's old
      pool leaves are deleted by the call;
    * attention reads the pages through the table
      (:func:`~mpi4torch_tpu.ops.paged_attention.
      paged_decode_attention`).  On a TPU, for eligible shapes, that is
      a kernel that fetches each slot's pages up to its frontier and
      no others, with an online softmax: equal to the dense step to
      rounding.  Everywhere else it gathers each slot's full
      ``max_seq`` extent — written rows bit-identical to the dense
      cache's, unmapped pages as zeros behind the per-row causal
      frontier — and attends exactly as the dense step does: bitwise
      equal to it.  Backend and shapes decide; there is no option.

    The table rides as DATA: one compiled program for every
    alloc/free/COW/prefix-sharing state of the pool, the same
    no-retrace contract the dense slot table holds, now holding under
    page churn too.

    The caller (the engine's host-side
    :class:`~mpi4torch_tpu.serve.paging.BlockManager`) guarantees live
    slots' write cells are distinct private pages — the copy-on-write
    discipline — which is ``block_scatter``'s exactness invariant.
    Free slots carry ``-1`` write pages and an ``active=False`` mask:
    no write, no page read, zero attention rows, payload rows zeroed
    before the wire (the dense step's rule)."""
    pos = jnp.asarray(pos, jnp.int32)
    classes = page_classes(cfg)
    tables = table if isinstance(table, dict) \
        else dict.fromkeys(sorted(set(classes) - {None}), table)
    tables = {c: jnp.asarray(t, jnp.int32) for c, t in tables.items()}
    bs = first_paged_leaf(pool).shape[1]
    live = _live_rows(active)
    reduce = _decode_reduce(comm, live, overlap, algorithm)
    write = _block_scatter_donated if donate else block_scatter

    # The slot's current write page in each class and its in-page
    # offset; a free slot's all--1 table row yields -1, which
    # block_scatter drops.
    wbs = {c: jnp.take_along_axis(
        t, jnp.clip(pos // bs, 0, t.shape[1] - 1)[:, None],
        axis=1)[:, 0] for c, t in tables.items()}
    off = pos % bs
    of_class = lambda layer: (tables[classes[layer]], wbs[classes[layer]])

    def attend(layer, q, k, v, window):
        # One row a live slot scattered into its page; attention reads
        # the pages through the table of the layer's class.
        c, (table, wb) = pool[layer], of_class(layer)
        pk = write(c["k"], wb, off, k[:, 0], live)
        pv = write(c["v"], wb, off, v[:, 0], live)
        o = paged_decode_attention(
            q[:, 0], pk, pv, table, pos, window=window, active=live)
        return o, {"k": pk, "v": pv}

    def attend_latent(layer, q, rows, lat, selected):
        # One latent row a live slot scattered into its page; the read
        # is absorbed, page by page through the table, or, under a
        # selection, of the rows it names and no others.
        table, wb = of_class(layer)
        pc = write(pool[layer]["c"], wb, off, rows[:, 0], live)
        if selected is None:
            u = paged_latent_attention(
                lat.absorbed(q[:, 0]), pc, table, pos,
                v_width=lat.spec.kv_rank, scale=lat.scale, active=live)
        else:
            u = paged_sparse_latent_attention(
                lat.absorbed(q[:, 0]), pc, table, selected,
                v_width=lat.spec.kv_rank, scale=lat.scale)
        return lat.values(u), {"c": pc}

    def select(layer, q_i, k_i, w, ix):
        # One index key a live slot scattered into its page; every slot
        # scores its pages up to its frontier and keeps the top_k
        # positions.
        table, wb = of_class(layer)
        pk = write(pool[layer]["ik"], wb, off, k_i[:, 0], live)
        scores = paged_index_scores(q_i[:, 0], w[:, 0], pk, table, pos,
                                    active=live)
        return select_rows(scores, _frontier(pos, scores.shape[1], live),
                           ix.top_k), pk

    with serve_step_scope("decode_step"):
        x = embed_tokens(cfg, shards, tokens)
        if cfg.pos_table:
            x = x + jnp.take(shards["pos"], pos, axis=0)
        x, new_pool, counts = _walk_layers(
            cfg, shards, x, pos, attend, attend_latent, reduce, live,
            select, _state_view(pool, "ssm_update", carried=True))
        _hand_out(stats, counts)
        return x @ shards["unembed"], new_pool


def admit_zero3(cfg: TransformerConfig, comm, p_shards, template, *,
                dtype=None, strategy=None):
    """Admit a ZeRO-3-trained checkpoint into serving TP shards — the
    train→serve boundary recipe, on the planned
    :meth:`~mpi4torch_tpu.MPI_Communicator.Reshard` path
    (``parallel.zero.zero3_to_tp``), never the
    gather-everything-everywhere default.

    Per-leaf routing: ``wo``/``w2`` take the row-shard Layout and
    ``w1`` (gelu) the column-shard Layout — each ONE planned
    all-to-all-class exchange, ``O(shard)`` peak; ``wqkv`` (its q/k/v
    head blocks interleave per rank — not an axis-contiguous shard the
    chunk-grid planner can express) and swiglu's fused ``w1`` ride the
    replicated Layout (the documented planned-gather leg) and are
    column-sliced locally; embeddings/norms/unembedding replicate.
    ``dtype`` is the serving-precision override (bf16 shards under f32
    training state), applied by ``zero3_to_tp`` after the exchange.

    Returns the :func:`shard_params_tp`-layout serve tree, bitwise
    equal to ``shard_params_tp(cfg, zero3_params(...), comm)`` — the
    redistribution moves bits, never rounds them (pre-``dtype``)."""
    from .. import reshard as _rs
    from ..parallel.zero import zero3_to_tp

    import re as _re

    size = comm.size
    validate_tp(cfg, size)
    if cfg.layers:
        raise CommError(
            "admit_zero3: the ZeRO-3 admission routes the leaves of the "
            "configuration's own layers; a per-layer spec's leaves have "
            "no route yet — construct the engine from the gathered tree")
    row = _rs.Layout((size,), ((0,), ()))
    col = _rs.Layout((size,), ((), (0,)))

    # Path-routed Layout rules in the reshard/rules.py mold; everything
    # unmatched — embeddings, positional table, norms, unembedding, and
    # the head-interleaved fused projections — replicates.
    rules = [
        (r"blocks/\d+/wo$", row),
        (r"blocks/\d+/w2$", row),
    ]
    if cfg.ffn != "swiglu":
        rules.append((r"blocks/\d+/w1$", col))

    def lay_for(path, leaf):
        shape = jnp.shape(leaf)
        for pat, lay in rules:
            if _re.search(pat, path) and len(shape) == len(lay.spec):
                return lay
        return _rs.Layout((size,), ((),) * len(shape))

    paths = _rs.tree_paths(template)
    specs = jax.tree.map(lay_for, paths, template)
    tp_tree = zero3_to_tp(comm, p_shards, template, specs,
                          strategy=strategy, dtype=dtype)

    # Local post-pass: the replicated-admitted fused projections take
    # their head-aligned column slices here (pure slicing — bitwise),
    # through the SAME layout helpers shard_params_tp cuts with.
    out_blocks = []
    for blk in tp_tree["blocks"]:
        nb = dict(blk)
        nb["wqkv"] = _shard_wqkv(cfg, comm, blk["wqkv"])
        if cfg.ffn == "swiglu":
            nb["w1"] = _shard_swiglu_w1(cfg, comm, blk["w1"])
        out_blocks.append(nb)
    out = {k: v for k, v in tp_tree.items() if k != "blocks"}
    out["blocks"] = out_blocks
    return out
