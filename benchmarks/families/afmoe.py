"""The ``afmoe`` family (Trinity-Mini) for the ``serve_family`` traffic
kind: gated, QK-normed grouped-query attention stated on every layer's
spec, sliding (rotated, a window) or full (no rotation) by the
configuration's ``layer_types``; a norm before and after each branch; a
dense swiglu FFN in the leading layers and a sigmoid top-k expert layer
with one shared expert in the others, ALL of whose experts this chip
holds.

What a family gives that kind is listed in ``families/openpangu_moe.py``;
the engine and the top's layout are taken from there, and this file
holds what differs:

* ``transformer_config``: the published keys as the program's per-layer
  spec, a ``GQA`` mixer a layer (window 0 and no rotation on a full
  layer), the embedding's ``sqrt(hidden_size)`` under ``mup_enabled``;
* ``make_top`` / ``make_layer`` / ``layer_maker`` / ``make_params``:
  seeded weights in the program's layout, a layer at a time;
* ``scopes``: ``attn_window`` before ``attn``, the whole-mixer scope with
  its separator: an instruction under the window layers' cache write and
  read keeps that scope's name, every other instruction of the attention
  mixers (projections, norms, rotation, gate, output projection, and the
  full layer's write and read) reads ``attn``; the two shares add up to
  the mixers';
* ``kernel_calls`` with ``paged_read_cost`` and ``grouped_dot_cost``:
  the benchmark's own counts of the paged K/V read and the grouped
  products, from the traced steps' own counters.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmarks.families.openpangu_moe import build_engine  # noqa: F401
from benchmarks.weights import _dense, _scale, seed_key

# The grouped products compile to custom calls whose op_name the compiler
# writes itself (``ragged-dot-none``); only the expert layer makes them.
KERNELS = {"ragged-dot": "moe"}
_TOP, _LAYER = 0, 1
# The scales of the seeded weights.  Every branch ends in a norm, so a
# branch's size in the stream is its post-norm's scale and nothing else
# (openPangu's family has the argument): those scales start at 1 over
# the square root of the published depth.  The embedding rows are N(0, 1
# / hidden): under ``mup_enabled`` the model multiplies them by
# sqrt(hidden), and the stream then starts at unit variance, as a model
# trained under that parametrisation has it.  The stream of these five
# layers is mostly the token's own embedding (ten branches add 0.31 to a
# variance of 1), each token meets its own router input, and a router
# drawn N(0, 1 / fan-in) spreads uniformly drawn tokens evenly over its
# 128 experts.  No output projection is cut to a tenth: the post-norm
# behind it takes any such factor out again.
_DEPTH = 32


def layer_is_dense(cfg: dict, index: int) -> bool:
    return index < cfg["num_dense_layers"]


def layer_is_sliding(cfg: dict, index: int) -> bool:
    return cfg["layer_types"][index] == "sliding_attention"


# ---------------------------------------------------------------- program

def scopes() -> dict:
    """``{"attn_window" | "attn" | "moe" | ...: what the op_name
    holds}``, from the program; the order decides (see the module's
    text)."""
    from mpi4torch_tpu.utils.profiling import LAYER_SCOPES

    own = {"attn_window": LAYER_SCOPES["attn_window"],
           "attn": LAYER_SCOPES["attn"] + "/"}
    own.update((k, v) for k, v in LAYER_SCOPES.items()
               if not k.startswith(("attn", "ssm_")))
    return own


def kernel_names() -> dict:
    from mpi4torch_tpu.ops import paged_attention

    return {"paged_attn": paged_attention.KERNEL_NAMES[0]}


def transformer_config(cfg: dict, remat: bool = False):
    from mpi4torch_tpu.models.transformer import (GQA, LayerSpec,
                                                  TransformerConfig)
    from mpi4torch_tpu.parallel.moe import Experts

    n = cfg["num_hidden_layers"]
    if cfg["rms_norm_eps"] != 1e-5:
        raise ValueError("the program's rmsnorm fixes eps at 1e-5; the "
                         f"configuration states {cfg['rms_norm_eps']}")
    if cfg["score_func"] != "sigmoid" or cfg["hidden_act"] != "silu" \
            or cfg["tie_word_embeddings"] or cfg["rope_scaling"] \
            or cfg["n_group"] != 1 or cfg["topk_group"] != 1 \
            or cfg["num_expert_groups"] != 1 \
            or cfg["num_limited_groups"] != 1 \
            or len(cfg["layer_types"]) != n \
            or set(cfg["layer_types"]) - {"sliding_attention",
                                          "full_attention"}:
        raise ValueError(
            "afmoe: built are sliding and full attention layers (one of "
            "layer_types a layer), sigmoid top-k routing in one group, "
            "silu, an untied head and no rope scaling")
    share = cfg["deployment_share"]
    experts = Experts(
        n_experts=cfg["num_experts"],
        top_k=cfg["num_experts_per_tok"],
        d_expert=cfg["moe_intermediate_size"],
        first_expert=share["first_expert"], n_held=cfg["num_experts"],
        n_shared=cfg["num_shared_experts"], scale=float(cfg["route_scale"]),
        score="sigmoid", renorm=bool(cfg["route_norm"]))

    def mixer(sliding: bool):
        return GQA(n_heads=cfg["num_attention_heads"],
                   n_kv_heads=cfg["num_key_value_heads"],
                   head_dim=cfg["head_dim"],
                   window=cfg["sliding_window"] if sliding else 0,
                   rope=sliding, rope_theta=float(cfg["rope_theta"]),
                   qk_norm=True, gate=True)

    layers = tuple(
        LayerSpec(mixer=mixer(layer_is_sliding(cfg, i)),
                  ffn=None if layer_is_dense(cfg, i) else experts,
                  post_norm=True) for i in range(n))
    # nope: no position table; a sliding layer's mixer rotates its own
    # queries and keys, a full layer has no position signal of its own.
    return TransformerConfig(
        vocab=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], n_layers=n,
        d_ff=cfg["intermediate_size"],
        max_seq=cfg["max_position_embeddings"], nope=True, norm="rmsnorm",
        ffn="swiglu", remat=remat, layers=layers,
        embed_scale=math.sqrt(cfg["hidden_size"]) if cfg["mup_enabled"]
        else 1.0)


# ---------------------------------------------------------------- weights

def _post_scale(key, d, dtype):
    return (_scale(key, d, jnp.float32) / jnp.sqrt(jnp.float32(_DEPTH))
            ).astype(dtype)


def _mixer_leaves(key, cfg, dtype):
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    h, h_kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    k = jax.random.split(key, 4)
    return {"wqkv": _dense(k[0], d, (2 * h + 2 * h_kv) * hd, dtype),
            "q_norm": {"scale": _scale(k[1], hd, dtype)},
            "k_norm": {"scale": _scale(k[2], hd, dtype)},
            "wo": _dense(k[3], h * hd, d, dtype)}


def _expert_leaves(key, cfg, dtype):
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    held = width = cfg["num_experts"]
    shared = cfg["num_shared_experts"] * f
    k = jax.random.split(key, 5)
    stack = lambda key, m, n: jax.vmap(
        lambda kk: _dense(kk, m, n, dtype))(jax.random.split(key, held))
    return {"router": _dense(k[0], d, width, dtype),
            # The selection bias (assumed zeros).
            "bias": jnp.zeros((width,), dtype),
            "w1": stack(k[1], d, 2 * f), "w2": stack(k[2], f, d),
            "shared_w1": _dense(k[3], d, 2 * shared, dtype),
            "shared_w2": _dense(k[4], shared, d, dtype)}


def make_layer(key, cfg: dict, index: int, dtype):
    """Leaves of decoder layer ``index`` (0-based) in the program's
    layout: ``mixer.wqkv``'s columns are ``[q | k | v | g]``, fused
    swiglu ``w1`` = [gate | up], experts stacked on axis 0."""
    d = cfg["hidden_size"]
    k = jax.random.split(jax.random.fold_in(
        jax.random.fold_in(key, _LAYER), index), 7)
    blk = {"ln1": {"scale": _scale(k[0], d, dtype)},
           "ln1_post": {"scale": _post_scale(k[1], d, dtype)},
           "ln2": {"scale": _scale(k[2], d, dtype)},
           "ln2_post": {"scale": _post_scale(k[3], d, dtype)},
           "mixer": _mixer_leaves(k[4], cfg, dtype)}
    if layer_is_dense(cfg, index):
        f = cfg["intermediate_size"]
        blk["w1"] = _dense(k[5], d, 2 * f, dtype)
        blk["w2"] = _dense(k[6], f, d, dtype)
    else:
        blk["experts"] = _expert_leaves(k[5], cfg, dtype)
    return blk


def make_top(key, cfg: dict, dtype):
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    k = jax.random.split(jax.random.fold_in(key, _TOP), 3)
    std = d ** -0.5 if cfg["mup_enabled"] else 1.0
    return {"embed": (std * jax.random.normal(k[0], (v, d), jnp.float32)
                      ).astype(dtype),
            "ln_f": {"scale": _scale(k[1], d, dtype)},
            "unembed": _dense(k[2], d, v, dtype)}


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

def paged_read_cost(cfg: dict, live_pages: int, block_size: int,
                    itemsize: int = 2) -> tuple:
    """(FLOP, bytes) of the paged K/V read of one decode step in ONE
    layer whose class of pages has ``live_pages`` pages live for the
    step's slots (a full layer: every page up to the frontiers; a
    sliding layer: the pages its windows touch): a K page and a V page
    are fetched once each and every row of the pair meets every query
    head twice, as key and as value.  Rows behind a frontier or a
    window's edge inside their page are counted, as the MXU runs them;
    the queries, outputs and the tables are under 0.1% of the bytes and
    left out.  Memory bound: 8 FLOP a byte at 8 query heads a KV head."""
    rows = live_pages * block_size
    hd = cfg["head_dim"]
    return (4 * rows * cfg["num_attention_heads"] * hd,
            2 * rows * cfg["num_key_value_heads"] * hd * itemsize)


def grouped_dot_cost(cfg: dict, rows, itemsize: int = 2) -> list:
    """[(FLOP, bytes)] of the two grouped products of one expert layer in
    one program call whose held experts took ``rows`` (held,): the fused
    gate and up matrices (``hidden x 2 width``), then the down matrices
    (``width x hidden``).  Each needs 2 FLOP a held row and matrix
    element, and moves at least the held rows of its row operands and the
    matrix of every expert that took a row, once."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    r = int(sum(int(x) for x in rows))
    touched = sum(1 for x in rows if int(x) > 0)
    return [(2 * r * k * n, itemsize * (r * k + r * n + touched * k * n))
            for k, n in ((d, 2 * f), (f, d))]


def kernel_calls(cfg: dict, steps: list, block_size: int) -> dict:
    """``{kernel: {"events", "calls", "beside"}}`` (what
    ``readers/kernel_roofline.py`` takes) for the traced phase's step
    records ``steps``.  A decode step (``active > 0``) is one call of the
    paged read a layer: a sliding layer's over the pages the step's
    ``window_pages_held`` counts (what the live slots hold in the window
    class), a full layer's over the rest of its ``decode_pages_live``
    (the sum over the two tables).  A program that counts no
    ``window_pages_held`` has no window class, and nothing is counted
    for it.  Every ``(program, rows)`` of a step's ``moe_rows`` (prefills
    and the decode step, in the order they ran) is two grouped products
    an expert layer."""
    n = cfg["num_hidden_layers"]
    sliding = sum(layer_is_sliding(cfg, i) for i in range(n))
    paged, grouped = [], []
    for r in steps:
        if r.get("active", 0) > 0 and r.get("window_pages_held", 0) > 0:
            held = r["window_pages_held"]
            paged += [paged_read_cost(cfg, held, block_size)] * sliding
            paged += [paged_read_cost(cfg, r["decode_pages_live"] - held,
                                      block_size)] * (n - sliding)
        for _, rows in r.get("moe_rows", ()):
            for layer in rows:
                grouped += grouped_dot_cost(cfg, layer)
    return {
        "paged_attn": {"events": kernel_names()["paged_attn"],
                       "calls": paged},
        "moe_grouped_dot.serve": {
            "events": "ragged-dot-none", "calls": grouped,
            "beside": "ragged-dot-metadata"}}
