"""The ``longcat_flash`` family for the ``serve_family`` traffic kind: a
published layer is a double block with one shortcut-connected expert
layer, routed by a softmax over routed and zero-compute experts.

What a family gives that kind is listed in ``families/openpangu_moe.py``;
what is the same for both (the engine, the scopes, the top of the tree,
the two kernels and their counts) is taken from there, and this file
holds what differs:

* ``transformer_config``: eight spec'd layers for four published ones,
  each rotated latent attention with the two scaled latents and a dense
  FFN; the even ones carry the expert layer as a ``branch``, the odd ones
  ``join`` it;
* ``make_layer`` / ``layer_maker`` / ``make_params``: seeded weights in
  the program's layout, a spec'd layer at a time;
* ``kernel_calls``: the latent read at 64 heads, eight calls a decode
  step, and the grouped products of the four expert layers, under this
  configuration's key for the experts' width.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.families import openpangu_moe
from benchmarks.families.openpangu_moe import (KERNELS, build_engine,
                                               make_top, scopes)
from benchmarks.weights import _dense, _scale, seed_key

_LAYER = 1
# The scales of the seeded weights, by the rule the other two families
# follow: embedding rows N(0, 1), matrices N(0, 1 / fan-in), and every
# matrix that ends a branch (``wo``, the dense ``w2``, the experts'
# ``w2``) a tenth of that, so that the stream of these eight sublayers
# stays mostly the token's own embedding.  Then each token meets its own
# router input, a router drawn N(0, 1 / fan-in) gives every one of its
# 768 outputs the same chance, and uniformly drawn tokens spread evenly
# over them: a third of the choices fall on the 256 zero-compute experts
# (the published average: 8 real experts of 12) and a held expert sees
# 32 x 12 / 768 = 0.5 rows a decode step.  A block here has no norm on a
# branch's output (openPangu's sandwich does that work there), so the
# matrices' scale has to.  On the chip (PR 34, TPU v5e, fourteen seeds):
# these scales were the first tried and were kept: a held expert took
# 0.490-0.506 rows a decode step, 6.3 of 16 held experts a layer took a
# row, and 33.3-33.5% of the live choices fell on zero-compute experts
# (limits/longcat-flash-chat.serve_scmoe_1k.json and PERF.md section 6
# have the readings).
_OUT = 0.1


def transformer_config(cfg: dict, remat: bool = False):
    from mpi4torch_tpu.models.transformer import (MLA, LayerSpec,
                                                  TransformerConfig)
    from mpi4torch_tpu.parallel.moe import Experts

    if cfg["rms_norm_eps"] != 1e-5:
        raise ValueError("the program's rmsnorm fixes eps at 1e-5; the "
                         f"configuration states {cfg['rms_norm_eps']}")
    if cfg["attention_method"] != "MLA" or cfg["attention_bias"] \
            or cfg["zero_expert_type"] != "identity" \
            or cfg["norm_topk_prob"] or cfg["tie_word_embeddings"] \
            or cfg["hidden_act"] != "silu" \
            or cfg["num_hidden_layers"] != 2 * cfg["num_layers"] \
            or cfg["router_outputs"] != cfg["zero_expert_num"] \
            + cfg["published"]["n_routed_experts"]:
        raise ValueError(
            "longcat_flash: built are latent attention without biases, "
            "identity zero-compute experts, softmax top-k routing that is "
            "not renormalised, silu, an untied head, two spec'd layers "
            "(num_hidden_layers) for every published one (num_layers), and "
            "a router output for every routed and zero-compute expert")
    h = cfg["num_attention_heads"]
    d = cfg["hidden_size"]
    q_scale = (d / cfg["q_lora_rank"]) ** 0.5 if cfg["mla_scale_q_lora"] \
        else 1.0
    kv_scale = (d / cfg["kv_lora_rank"]) ** 0.5 if cfg["mla_scale_kv_lora"] \
        else 1.0
    mla = MLA(n_heads=h, kv_rank=cfg["kv_lora_rank"],
              qk_nope=cfg["qk_nope_head_dim"], qk_rope=cfg["qk_rope_head_dim"],
              v_dim=cfg["v_head_dim"], q_rank=cfg["q_lora_rank"], rope=True,
              q_scale=q_scale, kv_scale=kv_scale)
    experts = Experts(
        n_experts=cfg["published"]["n_routed_experts"],
        top_k=cfg["moe_topk"], d_expert=cfg["expert_ffn_hidden_size"],
        scale=float(cfg["routed_scaling_factor"]),
        first_expert=cfg["deployment_share"]["first_expert"],
        n_held=cfg["n_routed_experts"], score="softmax", renorm=False,
        n_zero=cfg["zero_expert_num"])
    layers = (LayerSpec(mixer=mla, branch=experts),
              LayerSpec(mixer=mla, join=True)) * cfg["num_layers"]
    return TransformerConfig(
        vocab=cfg["vocab_size"], d_model=d, n_heads=h,
        n_layers=len(layers), d_ff=cfg["ffn_hidden_size"],
        max_seq=cfg["max_position_embeddings"], rope=True,
        rope_theta=float(cfg["rope_theta"]), norm="rmsnorm", ffn="swiglu",
        remat=remat, layers=layers)


# ---------------------------------------------------------------- weights

def _out(key, m, n, dtype):
    return (_OUT * _dense(key, m, n, jnp.float32)).astype(dtype)


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
            "wo": _out(k[6], h * dv, d, dtype)}


def _branch_leaves(key, cfg, dtype):
    d, f = cfg["hidden_size"], cfg["expert_ffn_hidden_size"]
    held = cfg["n_routed_experts"]
    width = cfg["published"]["n_routed_experts"] + cfg["zero_expert_num"]
    k = jax.random.split(key, 3)
    stack = lambda make, key, m, n: jax.vmap(
        lambda kk: make(kk, m, n, dtype))(jax.random.split(key, held))
    return {"router": _dense(k[0], d, width, dtype),
            # The selection bias a PID rule would steer (assumed zeros).
            "bias": jnp.zeros((width,), dtype),
            "w1": stack(_dense, k[1], d, 2 * f),
            "w2": stack(_out, k[2], f, d)}


def make_layer(key, cfg: dict, index: int, dtype):
    """Leaves of spec'd layer ``index`` (0-based; published layer
    ``index // 2``) in the program's layout: fused swiglu ``w1`` = [gate |
    up], experts stacked on axis 0, ``wb``'s columns per head [k_nope |
    v]; an even layer carries the expert layer's leaves as ``branch``."""
    d, f = cfg["hidden_size"], cfg["ffn_hidden_size"]
    k = jax.random.split(jax.random.fold_in(
        jax.random.fold_in(key, _LAYER), index), 6)
    blk = {"ln1": {"scale": _scale(k[0], d, dtype)},
           "ln2": {"scale": _scale(k[1], d, dtype)},
           "mixer": _mixer_leaves(k[2], cfg, dtype),
           "w1": _dense(k[3], d, 2 * f, dtype),
           "w2": _out(k[4], f, d, dtype)}
    if index % 2 == 0:
        blk["branch"] = _branch_leaves(k[5], cfg, dtype)
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

def kernel_calls(cfg: dict, steps: list, block_size: int) -> dict:
    """``openpangu_moe.kernel_calls`` for this configuration: the same
    two kernels (``latent_read_cost`` at this configuration's 64 heads,
    one call a spec'd layer and decode step; ``grouped_dot_cost`` for the
    rows of every ``moe_rows`` entry), with the experts' width under the
    key those counts read."""
    return openpangu_moe.kernel_calls(
        {**cfg, "moe_intermediate_size": cfg["expert_ffn_hidden_size"]},
        steps, block_size)
