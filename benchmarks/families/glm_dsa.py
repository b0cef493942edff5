"""The ``glm_dsa`` family for the ``serve_family`` traffic kind: rotated
latent attention that reads only the rows a learned indexer selects,
the selection shared by the layers above a scoring one, a dense FFN in
the leading layer and the held share of a sigmoid top-k expert layer in
the others.

What a family gives that kind is listed in ``families/openpangu_moe.py``;
what is the same for both (the engine, the top of the tree, the expert
layer's leaves and the grouped products' count) is taken from there,
and this file holds what differs:

* ``transformer_config``: the published keys as the program's per-layer
  spec, the mixer's ``index`` an ``Indexer`` where ``indexer_types`` says
  ``"full"`` and ``"shared"`` where it says so; no norm behind a branch;
* ``make_layer`` / ``layer_maker`` / ``make_params``: seeded weights in
  the program's layout, a layer at a time, the indexer's among them;
* ``kernel_calls`` with ``sparse_read_cost`` and ``index_score_cost``:
  the benchmark's own counts of the decode step's two new reads, from
  what the equations need of a step's own counters (``dsa_rows_read``,
  ``dsa_rows_scored``) and not from how the program reads.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.families.openpangu_moe import (build_engine,
                                               grouped_dot_cost, make_top,
                                               scopes)
from benchmarks.weights import _dense, _scale, seed_key

# Instructions found by their own name: the grouped products' custom
# calls (the compiler drops their op_name), the expert layer's.
KERNELS = {"ragged-dot": "moe"}
_LAYER = 1
# The scales of the seeded weights, by the families' rule: embedding
# rows N(0, 1), matrices N(0, 1 / fan-in), and the matrices that end an
# FFN branch (the dense ``w2``, the experts' and the shared expert's
# ``w2``) a tenth of that, so that the stream of these five layers stays
# mostly the token's own embedding, each token meets its own router
# input, and a router drawn N(0, 1 / fan-in) spreads uniformly drawn
# tokens evenly over its 256 experts.  ``wo`` is NOT cut to a tenth
# here: a query's output is a softmax mean over 2,048 rows of unit
# values, about 1/27 a channel, and at a tenth of ``wo`` the whole
# attention branch would be 0.4% of the stream, as small as bfloat16's
# own rounding, so that the comparison with the reference could not
# tell a program that attends the wrong rows from a sound one.  At
# N(0, 1 / fan-in) the branch is some 4% of the stream a layer (five of
# them leave routing by token), and a wrong selection moves the logits
# as far as float8 products do.  The indexer's matrices follow the same
# rule; its key norm's bias is drawn 0.1 N(0, 1), so that it is there.
_OUT = 0.1


def layer_is_dense(cfg: dict, index: int) -> bool:
    return cfg["mlp_layer_types"][index] == "dense"


# ---------------------------------------------------------------- program

def kernel_names() -> dict:
    from mpi4torch_tpu.ops import paged_attention

    return {"paged_index_score": paged_attention.KERNEL_NAMES[2],
            "paged_sparse_latent_attn": paged_attention.KERNEL_NAMES[3]}


def transformer_config(cfg: dict, remat: bool = False):
    from mpi4torch_tpu.models.transformer import (MLA, Indexer, LayerSpec,
                                                  TransformerConfig)
    from mpi4torch_tpu.parallel.moe import Experts

    n = cfg["num_hidden_layers"]
    if cfg["rms_norm_eps"] != 1e-5:
        raise ValueError("the program's rmsnorm fixes eps at 1e-5; the "
                         f"configuration states {cfg['rms_norm_eps']}")
    if cfg["attention_bias"] or cfg["tie_word_embeddings"] \
            or cfg["hidden_act"] != "silu" or not cfg["norm_topk_prob"] \
            or cfg["n_shared_experts"] != 1 or cfg["n_group"] != 1 \
            or cfg["scoring_func"] != "sigmoid" \
            or cfg["num_nextn_predict_layers"] \
            or len(cfg["indexer_types"]) != n \
            or len(cfg["mlp_layer_types"]) != n \
            or cfg["qk_head_dim"] != cfg["qk_nope_head_dim"] \
            + cfg["qk_rope_head_dim"] \
            or [t == "dense" for t in cfg["mlp_layer_types"]] \
            != [i < cfg["first_k_dense_replace"] for i in range(n)]:
        raise ValueError(
            "glm_dsa: built are latent attention without biases, sigmoid "
            "top-k routing in one group, renormalised, with one shared "
            "expert, silu, an untied head, no multi-token-prediction "
            "module, one indexer_types and one mlp_layer_types entry a "
            "layer, the dense layers leading")
    h = cfg["num_attention_heads"]
    index = Indexer(n_heads=cfg["index_n_heads"],
                    head_dim=cfg["index_head_dim"],
                    rope=cfg["qk_rope_head_dim"], top_k=cfg["index_topk"])
    mla = lambda kind: MLA(
        n_heads=h, kv_rank=cfg["kv_lora_rank"],
        qk_nope=cfg["qk_nope_head_dim"], qk_rope=cfg["qk_rope_head_dim"],
        v_dim=cfg["v_head_dim"], q_rank=cfg["q_lora_rank"], rope=True,
        index={"full": index, "shared": "shared"}[kind])
    experts = Experts(
        n_experts=cfg["published"]["n_routed_experts"],
        top_k=cfg["num_experts_per_tok"],
        d_expert=cfg["moe_intermediate_size"],
        n_shared=cfg["n_shared_experts"],
        scale=float(cfg["routed_scaling_factor"]),
        first_expert=cfg["deployment_share"]["first_expert"],
        n_held=cfg["n_routed_experts"])
    layers = tuple(
        LayerSpec(mixer=mla(cfg["indexer_types"][i]),
                  ffn=None if layer_is_dense(cfg, i) else experts)
        for i in range(n))
    return TransformerConfig(
        vocab=cfg["vocab_size"], d_model=cfg["hidden_size"], n_heads=h,
        n_layers=n, d_ff=cfg["intermediate_size"],
        max_seq=cfg["max_position_embeddings"], rope=True,
        rope_theta=float(cfg["rope_parameters"]["rope_theta"]),
        norm="rmsnorm", ffn="swiglu", remat=remat, layers=layers)


# ---------------------------------------------------------------- weights

def _out(key, m, n, dtype):
    return (_OUT * _dense(key, m, n, jnp.float32)).astype(dtype)


def _index_leaves(key, cfg, dtype):
    d, q_rank = cfg["hidden_size"], cfg["q_lora_rank"]
    n_i, d_i = cfg["index_n_heads"], cfg["index_head_dim"]
    k = jax.random.split(key, 5)
    return {"wq": _dense(k[0], q_rank, n_i * d_i, dtype),
            "wk": _dense(k[1], d, d_i, dtype),
            "k_norm": {"scale": _scale(k[2], d_i, dtype),
                       "bias": (0.1 * jax.random.normal(
                           k[3], (d_i,), jnp.float32)).astype(dtype)},
            "ww": _dense(k[4], d, n_i, dtype)}


def _mixer_leaves(key, cfg, index: int, dtype):
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    rank, q_rank = cfg["kv_lora_rank"], cfg["q_lora_rank"]
    k = jax.random.split(key, 8)
    p = {"wqa": _dense(k[0], d, q_rank, dtype),
         "q_norm": {"scale": _scale(k[1], q_rank, dtype)},
         "wq": _dense(k[2], q_rank, h * (dn + dr), dtype),
         "wa": _dense(k[3], d, rank + dr, dtype),
         "kv_norm": {"scale": _scale(k[4], rank, dtype)},
         "wb": _dense(k[5], rank, h * (dn + dv), dtype),
         "wo": _dense(k[6], h * dv, d, dtype)}
    if cfg["indexer_types"][index] == "full":
        p["index"] = _index_leaves(k[7], cfg, dtype)
    return p


def _expert_leaves(key, cfg, dtype):
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    held, width = cfg["n_routed_experts"], \
        cfg["published"]["n_routed_experts"]
    shared = cfg["n_shared_experts"] * f
    k = jax.random.split(key, 5)
    stack = lambda make, key, m, n: jax.vmap(
        lambda kk: make(kk, m, n, dtype))(jax.random.split(key, held))
    return {"router": _dense(k[0], d, width, dtype),
            # The selection bias of noaux_tc (assumed zeros).
            "bias": jnp.zeros((width,), dtype),
            "w1": stack(_dense, k[1], d, 2 * f),
            "w2": stack(_out, k[2], f, d),
            "shared_w1": _dense(k[3], d, 2 * shared, dtype),
            "shared_w2": _out(k[4], shared, d, dtype)}


def make_layer(key, cfg: dict, index: int, dtype):
    """Leaves of decoder layer ``index`` (0-based) in the program's
    layout: fused swiglu ``w1`` = [gate | up], experts stacked on axis
    0, ``wb``'s columns per head [k_nope | v]; a scoring layer's mixer
    carries the indexer's leaves under ``index``."""
    d = cfg["hidden_size"]
    k = jax.random.split(jax.random.fold_in(
        jax.random.fold_in(key, _LAYER), index), 5)
    blk = {"ln1": {"scale": _scale(k[0], d, dtype)},
           "ln2": {"scale": _scale(k[1], d, dtype)},
           "mixer": _mixer_leaves(k[2], cfg, index, dtype)}
    if layer_is_dense(cfg, index):
        f = cfg["intermediate_size"]
        blk["w1"] = _dense(k[3], d, 2 * f, dtype)
        blk["w2"] = _out(k[4], f, d, dtype)
    else:
        blk["experts"] = _expert_leaves(k[3], cfg, dtype)
    return blk


def layer_maker(cfg: dict, dtype):
    """``(key, index) -> leaves``, one compiled program per kind of
    layer."""
    return jax.jit(lambda key, i: make_layer(key, cfg, i, dtype),
                   static_argnums=1)


def make_params(cfg: dict, seed: int, dtype):
    """The tree the engine is constructed from: the top made now, the
    layers made one by one as ``["blocks"]`` is walked."""
    key = seed_key(seed)
    layer = layer_maker(cfg, dtype)
    p = jax.jit(lambda k: make_top(k, cfg, dtype))(key)
    p["blocks"] = (layer(key, i) for i in range(cfg["num_hidden_layers"]))
    return p


# ------------------------------------------------------------------ counts

def sparse_read_cost(cfg: dict, rows: int, itemsize: int = 2) -> tuple:
    """(FLOP, bytes) of the latent read of one decode step in one layer
    whose selections name ``rows`` rows over its live slots (``min(pos +
    1, index_topk)`` a slot): each named row is fetched once at its
    stored width (the latent and the shared key, up to whole lanes of
    128) and meets every head twice, as key at the stored width and as
    value at the latent's.  Nothing else of the pool is needed."""
    rank = cfg["kv_lora_rank"]
    width = -(-(rank + cfg["qk_rope_head_dim"]) // 128) * 128
    return (2 * rows * cfg["num_attention_heads"] * (width + rank),
            rows * width * itemsize)


def index_score_cost(cfg: dict, rows: int, itemsize: int = 2) -> tuple:
    """(FLOP, bytes) of the scoring of one decode step in one scoring
    layer whose live slots hold ``rows`` positions up to their frontiers:
    each position's index key is fetched once and meets every index head
    of its slot's query."""
    n_i, d_i = cfg["index_n_heads"], cfg["index_head_dim"]
    return 2 * rows * n_i * d_i, rows * d_i * itemsize


def kernel_calls(cfg: dict, steps: list, block_size: int) -> dict:
    """``{kernel: {"events", "calls", "beside" | "beside_scope"}}``
    (what ``readers/kernel_roofline.py`` and ``kernel_roofline_scoped.py``
    take) for the traced phase's step records ``steps``.  A decode step
    that counted ``dsa_rows_read`` is one call of the sparse read a
    layer (every layer is indexed, and each named the same rows' count:
    the step's total over the layers) with the gather that feeds it
    beside; one call of the scoring a ``"full"`` layer, from
    ``dsa_rows_scored``; every ``(program, rows)`` of a step's
    ``moe_rows`` is two grouped products an expert layer."""
    from mpi4torch_tpu.ops import paged_attention

    n = cfg["num_hidden_layers"]
    n_full = sum(t == "full" for t in cfg["indexer_types"])
    sparse, scoring, grouped = [], [], []
    for r in steps:
        if r.get("active", 0) > 0 and r.get("dsa_rows_read", 0) > 0:
            sparse += [sparse_read_cost(cfg, r["dsa_rows_read"] // n)] * n
            scoring += [index_score_cost(
                cfg, r["dsa_rows_scored"] // n_full)] * n_full
        for _, rows in r.get("moe_rows", ()):
            for layer in rows:
                grouped += grouped_dot_cost(cfg, layer)
    names = kernel_names()
    return {
        "paged_sparse_latent_attn": {
            "events": names["paged_sparse_latent_attn"], "calls": sparse,
            "beside_scope": paged_attention.SPARSE_GATHER_SCOPE},
        "paged_index_score": {
            "events": names["paged_index_score"], "calls": scoring},
        "moe_grouped_dot.serve": {
            "events": "ragged-dot-none", "calls": grouped,
            "beside": "ragged-dot-metadata"}}
