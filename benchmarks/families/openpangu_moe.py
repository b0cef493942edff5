"""The ``openpangu_moe`` family for the ``serve_family`` traffic kind:
what that kind takes from a family instead of from ``program.py``,
``weights.py`` and ``flops.py``, which know the dense decoder only.

* ``transformer_config``: the published keys as the program's
  ``TransformerConfig`` with a per-layer spec (rotated latent attention
  with a query rank, post-branch norms, the held expert share);
* ``make_top`` / ``make_layer`` / ``make_params``: seeded weights in the
  program's layout, a layer at a time: the plain reference asks for the
  same leaves one layer at a time, and ``make_params`` hands the engine
  a tree whose layers are made as the engine asks for them;
* ``build_engine``: ``serve.Engine(spmd=True)`` on that tree;
* ``kernel_calls`` with ``latent_read_cost`` and ``grouped_dot_cost``:
  the benchmark's own counts of the two kernels' work, from the traced
  steps' own counters;
* ``scopes``: the scope each mechanism's instructions run under, by the
  program's own names, and ``KERNELS``: instructions found by their own
  name.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.weights import _dense, _scale, seed_key

# The grouped products compile to custom calls whose op_name the compiler
# writes itself (``ragged-dot-none``); only the expert layer makes them.
KERNELS = {"ragged-dot": "moe"}
_TOP, _LAYER = 0, 1
# The scales of the seeded weights.  Every branch ends in a norm, so a
# branch's size in the stream is its post-norm's scale and nothing else:
# the sandwich norm of the Pangu Ultra report starts those scales at a
# constant over the square root of the depth, here 1 / sqrt(61) = 0.128.
# With embedding rows of unit variance the stream of these five layers
# is then mostly the token's own embedding (ten branches add 0.16 to a
# variance of 1), each token meets its own router input, and a router
# drawn N(0, 1/fan-in) spreads the tokens evenly over its 256 experts,
# as a balanced checkpoint does (Kimi's family says what weights without
# that property did: one expert took six sevenths of the rows).
_EMBED_STD = 1.0
_DEPTH = 61


def layer_is_dense(cfg: dict, index: int) -> bool:
    return index < cfg["first_k_dense_replace"]


# ---------------------------------------------------------------- program

def scopes() -> dict:
    """``{"mla" | "moe" | ...: the scope's name}``, from the program."""
    from mpi4torch_tpu.utils.profiling import LAYER_SCOPES

    return dict(LAYER_SCOPES)


def kernel_names() -> dict:
    from mpi4torch_tpu.ops import paged_attention

    return {"paged_latent_attn": paged_attention.KERNEL_NAMES[1]}


def transformer_config(cfg: dict, remat: bool = False):
    from mpi4torch_tpu.models.transformer import (MLA, LayerSpec,
                                                  TransformerConfig)
    from mpi4torch_tpu.parallel.moe import Experts

    if cfg["rms_norm_eps"] != 1e-5:
        raise ValueError("the program's rmsnorm fixes eps at 1e-5; the "
                         f"configuration states {cfg['rms_norm_eps']}")
    if not cfg["sandwich_norm"] or not cfg["norm_topk_prob"] \
            or cfg["n_shared_experts"] != 1 or cfg["attention_bias"] \
            or cfg["tie_word_embeddings"] or cfg["hidden_act"] != "silu" \
            or cfg["num_nextn_predict_layers"]:
        raise ValueError(
            "openpangu_moe: built are sandwich norms, renormalised sigmoid "
            "top-k routing with one shared expert, silu, no biases, an "
            "untied head and no multi-token-prediction module")
    h = cfg["num_attention_heads"]
    mla = MLA(n_heads=h, kv_rank=cfg["kv_lora_rank"],
              qk_nope=cfg["qk_nope_head_dim"], qk_rope=cfg["qk_rope_head_dim"],
              v_dim=cfg["v_head_dim"], q_rank=cfg["q_lora_rank"], rope=True)
    experts = Experts(
        n_experts=cfg["published"]["n_routed_experts"],
        top_k=cfg["num_experts_per_tok"],
        d_expert=cfg["moe_intermediate_size"],
        n_shared=cfg["n_shared_experts"],
        scale=float(cfg["routed_scaling_factor"]),
        first_expert=cfg["deployment_share"]["first_expert"],
        n_held=cfg["n_routed_experts"])
    n = cfg["num_hidden_layers"]
    layers = tuple(
        LayerSpec(mixer=mla, ffn=None if layer_is_dense(cfg, i) else experts,
                  post_norm=True) for i in range(n))
    # rope=True: no learned position table; the latent mixer rotates its
    # own 64 channels by rope_theta.
    return TransformerConfig(
        vocab=cfg["vocab_size"], d_model=cfg["hidden_size"], n_heads=h,
        n_layers=n, d_ff=cfg["intermediate_size"],
        max_seq=cfg["max_position_embeddings"], rope=True,
        rope_theta=float(cfg["rope_theta"]), norm="rmsnorm", ffn="swiglu",
        remat=remat, layers=layers)


def build_engine(tcfg, params, engine: dict, nranks: int):
    """``Engine(spmd=True)`` on a tree whose layers are made as the
    engine takes them (``make_params``): the leaves are arguments of the
    sharding programs, so nothing is lowered as a constant and the
    constructor runs jitted, as the program's users run it."""
    from mpi4torch_tpu import serve

    return serve.Engine(tcfg, params, serve.ServeConfig(**engine),
                        spmd=True, nranks=nranks)


# ---------------------------------------------------------------- weights

def _post_scale(key, d, dtype):
    return (_scale(key, d, jnp.float32) / jnp.sqrt(jnp.float32(_DEPTH))
            ).astype(dtype)


def _mixer_leaves(key, cfg, dtype):
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    rank, q_rank = cfg["kv_lora_rank"], cfg["q_lora_rank"]
    k = jax.random.split(key, 7)
    return {"wqa": _dense(k[0], d, q_rank, dtype),
            "q_norm": {"scale": _scale(k[1], q_rank, dtype)},
            "wq": _dense(k[2], q_rank, h * (dn + dr), dtype),
            "wa": _dense(k[3], d, rank + dr, dtype),
            "kv_norm": {"scale": _scale(k[4], rank, dtype)},
            "wb": _dense(k[5], rank, h * (dn + dv), dtype),
            "wo": _dense(k[6], h * dv, d, dtype)}


def _expert_leaves(key, cfg, dtype):
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    held, width = cfg["n_routed_experts"], \
        cfg["published"]["n_routed_experts"]
    shared = cfg["n_shared_experts"] * f
    k = jax.random.split(key, 5)
    stack = lambda key, m, n: jax.vmap(
        lambda kk: _dense(kk, m, n, dtype))(jax.random.split(key, held))
    return {"router": _dense(k[0], d, width, dtype),
            # No selection bias (assumed); the program's layer has the leaf.
            "bias": jnp.zeros((width,), dtype),
            "w1": stack(k[1], d, 2 * f), "w2": stack(k[2], f, d),
            "shared_w1": _dense(k[3], d, 2 * shared, dtype),
            "shared_w2": _dense(k[4], shared, d, dtype)}


def make_layer(key, cfg: dict, index: int, dtype):
    """Leaves of decoder layer ``index`` (0-based) in the program's
    layout: fused swiglu ``w1`` = [gate | up], experts stacked on axis
    0, ``wb``'s columns per head [k_nope | v]."""
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
    return {"embed": (_EMBED_STD * jax.random.normal(
                k[0], (v, d), jnp.float32)).astype(dtype),
            "ln_f": {"scale": _scale(k[1], d, dtype)},
            "unembed": _dense(k[2], d, v, dtype)}


def layer_maker(cfg: dict, dtype):
    """``(key, index) -> leaves``, one compiled program per kind of
    layer."""
    jitted = jax.jit(lambda key, i: make_layer(key, cfg, i, dtype),
                     static_argnums=1)
    return jitted


def make_params(cfg: dict, seed: int, dtype):
    """The tree the engine is constructed from: the top made now, the
    layers made one by one as ``["blocks"]`` is walked, so that no more
    than one layer of it exists beside the engine's own copy."""
    key = seed_key(seed)
    layer = layer_maker(cfg, dtype)
    p = jax.jit(lambda k: make_top(k, cfg, dtype))(key)
    p["blocks"] = (layer(key, i) for i in range(cfg["num_hidden_layers"]))
    return p


# ------------------------------------------------------------------ counts

def latent_read_cost(cfg: dict, live_pages: int, block_size: int,
                     itemsize: int = 2) -> tuple:
    """(FLOP, bytes) of the paged latent read of one decode step in one
    layer whose live slots hold ``live_pages`` pages up to their
    frontiers: every row of a live page is fetched once (its stored
    width: the latent and the shared key, up to whole lanes of 128) and
    meets every head twice, as key at the stored width and as value at
    the latent's.  Rows behind a frontier inside its page are counted,
    as the MXU runs them; the queries, outputs and the table are under
    0.1% of the bytes and left out."""
    rank = cfg["kv_lora_rank"]
    width = -(-(rank + cfg["qk_rope_head_dim"]) // 128) * 128
    rows = live_pages * block_size
    return (2 * rows * cfg["num_attention_heads"] * (width + rank),
            rows * width * itemsize)


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
    records ``steps``: a decode step (``active > 0``) is one call of the
    latent kernel a layer, from the step's own ``decode_pages_live``;
    every ``(program, rows)`` of a step's ``moe_rows`` (prefills and the
    decode step, in the order they ran) is two grouped products an
    expert layer."""
    latent, grouped = [], []
    for r in steps:
        if r.get("active", 0) > 0 and r.get("decode_pages_live", 0) > 0:
            latent += [latent_read_cost(cfg, r["decode_pages_live"],
                                        block_size)] \
                * cfg["num_hidden_layers"]
        for _, rows in r.get("moe_rows", ()):
            for layer in rows:
                grouped += grouped_dot_cost(cfg, layer)
    return {
        "paged_latent_attn": {
            "events": kernel_names()["paged_latent_attn"], "calls": latent},
        "moe_grouped_dot.serve": {
            "events": "ragged-dot-none", "calls": grouped,
            "beside": "ragged-dot-metadata"}}
