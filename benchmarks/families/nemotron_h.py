"""The ``nemotron_h`` family for the ``serve_family`` traffic kind:
layers of ONE part each, by the letters of ``hybrid_override_pattern``:
a Mamba-2 state-space mixer (``M``), the configuration's grouped-query
attention with no position signal (``*``), or the held share of a
sigmoid top-k expert layer whose routed experts are relu² in a latent
behind one shared down- and up-projection (``E``).

What a family gives that kind is listed in ``families/openpangu_moe.py``;
the engine and the top of the tree are taken from there, and this file
holds what differs:

* ``transformer_config``: the published keys as the program's per-layer
  spec: every ``LayerSpec`` with ``only``, ``nope`` attention;
* ``make_layer`` / ``layer_maker`` / ``make_params``: seeded weights in
  the program's layout, a layer at a time;
* ``scopes``: the whole-mixer scope with its separator, so that an
  instruction under one of the two scopes nested in it (whose names
  begin with its name) keeps that scope's name in what it is said to
  compute;
* ``kernel_calls`` with ``state_update_cost``, ``scan_cost`` and
  ``grouped_dot_cost``: the benchmark's own counts of the decode step's
  state update, the prefills' scan and the latent grouped products, from
  what the equations need of a step's own counters and not from how the
  program computes them.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.families.openpangu_moe import build_engine, make_top
from benchmarks.weights import _dense, _scale, seed_key

# Instructions found by their own name: the grouped products' custom
# calls (the compiler drops their op_name), the expert layer's.
KERNELS = {"ragged-dot": "moe"}
_LAYER = 1
# The scales of the seeded weights, by the families' rule: embedding
# rows N(0, 1), matrices N(0, 1 / fan-in), and the matrices that end a
# branch (the Mamba-2 ``out_proj``, the latent's ``up``, the shared
# expert's ``w2``) a tenth of that, so that the stream of these eleven
# layers stays mostly the token's own embedding, each token meets its own
# router input, and a router drawn N(0, 1 / fan-in) spreads uniformly
# drawn tokens evenly over its 512 experts.  The attention layer's ``wo``
# is NOT cut to a tenth, for GLM's reason (families/glm_dsa.py): a
# query's output is a softmax mean over hundreds of unit rows, a few
# hundredths a channel, and at a tenth of ``wo`` the one attention layer
# would be lost in bfloat16's rounding of the stream.
_OUT = 0.1


# ---------------------------------------------------------------- program

def scopes() -> dict:
    """``{"ssm" | "moe" | ...: what the op_name holds}``, from the
    program; ``ssm`` with its separator (see the module's text)."""
    from mpi4torch_tpu.utils.profiling import LAYER_SCOPES

    own = {k: v for k, v in LAYER_SCOPES.items()
           if not k.startswith("ssm_")}
    own["ssm"] = LAYER_SCOPES["ssm"] + "/"
    return own


def nested_scopes() -> dict:
    """The two scopes inside ``ssm``, by the program's own names."""
    from mpi4torch_tpu.utils.profiling import LAYER_SCOPES

    return {"ssm_state_update": LAYER_SCOPES["ssm_update"],
            "ssm_scan": LAYER_SCOPES["ssm_scan"]}


def transformer_config(cfg: dict, remat: bool = False):
    from mpi4torch_tpu.models.transformer import (LayerSpec, Mamba2,
                                                  TransformerConfig)
    from mpi4torch_tpu.parallel.moe import Experts

    n, pattern = cfg["num_hidden_layers"], cfg["hybrid_override_pattern"]
    if cfg["layer_norm_epsilon"] != 1e-5 or cfg["norm_eps"] != 1e-5:
        raise ValueError("the program's rmsnorm fixes eps at 1e-5; the "
                         f"configuration states {cfg['layer_norm_epsilon']}"
                         f" and {cfg['norm_eps']}")
    if cfg["attention_bias"] or cfg["mlp_bias"] or cfg["use_bias"] \
            or cfg["mamba_proj_bias"] or not cfg["use_conv_bias"] \
            or cfg["tie_word_embeddings"] or not cfg["norm_topk_prob"] \
            or cfg["mamba_hidden_act"] != "silu" \
            or cfg["mlp_hidden_act"] != "relu2" \
            or cfg["n_shared_experts"] != 1 or cfg["n_group"] != 1 \
            or cfg["topk_group"] != 1 or cfg["num_nextn_predict_layers"] \
            or cfg["sliding_window"] or len(pattern) != n \
            or set(pattern) - set("ME*") \
            or cfg["expand"] * cfg["hidden_size"] \
            != cfg["mamba_num_heads"] * cfg["mamba_head_dim"] \
            or cfg["num_attention_heads"] * cfg["head_dim"] \
            != cfg["hidden_size"]:
        raise ValueError(
            "nemotron_h: built are layers of one part by the letters M, E "
            "and * (one a layer), no bias but the convolution's, silu in "
            "the state-space mixer and relu² experts, sigmoid top-k "
            "routing in one group, renormalised, with one shared expert, "
            "an untied head, full attention whose heads fill the stream, "
            "and no multi-token-prediction module")
    mamba = Mamba2(n_heads=cfg["mamba_num_heads"],
                   head_dim=cfg["mamba_head_dim"],
                   d_state=cfg["ssm_state_size"], n_groups=cfg["n_groups"],
                   conv=cfg["conv_kernel"], chunk=cfg["chunk_size"])
    experts = Experts(
        n_experts=cfg["published"]["n_routed_experts"],
        top_k=cfg["num_experts_per_tok"],
        d_expert=cfg["moe_intermediate_size"],
        n_shared=cfg["n_shared_experts"],
        scale=float(cfg["routed_scaling_factor"]),
        first_expert=cfg["deployment_share"]["first_expert"],
        n_held=cfg["n_routed_experts"], latent=cfg["moe_latent_size"],
        act="relu2", d_shared=cfg["moe_shared_expert_intermediate_size"])
    spec = {"M": LayerSpec(mixer=mamba, only="mixer"),
            "E": LayerSpec(ffn=experts, only="ffn"),
            "*": LayerSpec(only="mixer")}
    # nope: neither a rotation nor a position table; the state-space
    # layers below an attention layer carry the order.
    return TransformerConfig(
        vocab=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], n_layers=n,
        d_ff=cfg["intermediate_size"],
        max_seq=cfg["max_position_embeddings"], nope=True, norm="rmsnorm",
        ffn="swiglu", remat=remat,
        layers=tuple(spec[letter] for letter in pattern))


# ---------------------------------------------------------------- weights

def _out(key, m, n, dtype):
    return (_OUT * _dense(key, m, n, jnp.float32)).astype(dtype)


def _mamba_leaves(key, cfg, dtype):
    d, h = cfg["hidden_size"], cfg["mamba_num_heads"]
    di = h * cfg["mamba_head_dim"]
    dc = di + 2 * cfg["n_groups"] * cfg["ssm_state_size"]
    taps = cfg["conv_kernel"]
    k = jax.random.split(key, 7)
    # exp(A_log) uniform in [1, 16]; softplus(dt_bias) log-uniform
    # between time_step_min and time_step_max, floored.
    dt = jnp.maximum(jnp.exp(jax.random.uniform(
        k[3], (h,), jnp.float32, jnp.log(cfg["time_step_min"]),
        jnp.log(cfg["time_step_max"]))), cfg["time_step_floor"])
    return {"in_proj": _dense(k[0], d, di + dc + h, dtype),
            # The taps N(0, 1 / taps): a unit input leaves at unit size.
            "conv": (jax.random.normal(k[1], (taps, dc), jnp.float32)
                     / jnp.sqrt(jnp.float32(taps))).astype(dtype),
            "conv_bias": (0.1 * jax.random.normal(
                k[2], (dc,), jnp.float32)).astype(dtype),
            "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
            "a_log": jnp.log(jax.random.uniform(
                k[4], (h,), jnp.float32, 1.0, 16.0)).astype(dtype),
            "d": jnp.ones((h,), dtype),
            "norm": {"scale": _scale(k[5], di, dtype)},
            "out_proj": _out(k[6], di, d, dtype)}


def _expert_leaves(key, cfg, dtype):
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    lat, shared = cfg["moe_latent_size"], \
        cfg["moe_shared_expert_intermediate_size"]
    held, width = cfg["n_routed_experts"], \
        cfg["published"]["n_routed_experts"]
    k = jax.random.split(key, 7)
    stack = lambda key, m, n: jax.vmap(
        lambda kk: _dense(kk, m, n, dtype))(jax.random.split(key, held))
    return {"router": _dense(k[0], d, width, dtype),
            # The selection bias (assumed zeros).
            "bias": jnp.zeros((width,), dtype),
            "down": _dense(k[1], d, lat, dtype),
            "w1": stack(k[2], lat, f), "w2": stack(k[3], f, lat),
            "up": _out(k[4], lat, d, dtype),
            "shared_w1": _dense(k[5], d, shared, dtype),
            "shared_w2": _out(k[6], shared, d, dtype)}


def make_layer(key, cfg: dict, index: int, dtype):
    """Leaves of layer ``index`` (0-based) in the program's layout, by
    its letter: one norm (``ln1`` for a mixer, ``ln2`` for an expert
    layer) and the one part's leaves; ``in_proj``'s columns are ``[z | x
    | B | C | dt]``, ``wqkv``'s ``[q | k | v]``, experts stacked on axis
    0, relu² experts have no gate (``w1`` is one matrix)."""
    d = cfg["hidden_size"]
    letter = cfg["hybrid_override_pattern"][index]
    k = jax.random.split(jax.random.fold_in(
        jax.random.fold_in(key, _LAYER), index), 3)
    if letter == "E":
        return {"ln2": {"scale": _scale(k[0], d, dtype)},
                "experts": _expert_leaves(k[1], cfg, dtype)}
    blk = {"ln1": {"scale": _scale(k[0], d, dtype)}}
    if letter == "M":
        blk["mixer"] = _mamba_leaves(k[1], cfg, dtype)
    else:
        kv = cfg["num_key_value_heads"] * cfg["head_dim"]
        blk["wqkv"] = _dense(k[1], d, d + 2 * kv, dtype)
        blk["wo"] = _dense(k[2], d, d, dtype)
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

def state_update_cost(cfg: dict, states: int, itemsize: int = 2) -> tuple:
    """(FLOP, bytes) of the decode step's state update over ``states``
    (slot, layer) pairs: each state element (float32) is read once and
    written once and takes 6 FLOP (decay, write, read-out: a multiply
    and an add each), and the convolution's kept inputs (``conv_kernel -
    1`` rows of the convolution's width) are read and written beside
    it.  Memory bound: 0.7 FLOP a byte."""
    h, p, n = cfg["mamba_num_heads"], cfg["mamba_head_dim"], \
        cfg["ssm_state_size"]
    dc = h * p + 2 * cfg["n_groups"] * n
    return (6 * states * h * p * n,
            states * (2 * h * p * n * 4
                      + 2 * (cfg["conv_kernel"] - 1) * dc * itemsize))


def scan_cost(cfg: dict, tokens: int, itemsize: int = 2) -> tuple:
    """(FLOP, bytes) of the chunked scan over ``tokens`` prompt tokens in
    ONE layer: a token meets its chunk's whole square (as the MXU runs
    it): ``C B^T`` a group and the weighted sum a head, 2 FLOP each, and
    the state twice (read out, written into); it moves its ``x`` and
    ``y``, its ``B`` and ``C`` and its step sizes once."""
    h, p, n = cfg["mamba_num_heads"], cfg["mamba_head_dim"], \
        cfg["ssm_state_size"]
    g, c = cfg["n_groups"], cfg["chunk_size"]
    return (tokens * (2 * c * (n * g + p * h) + 4 * h * p * n),
            tokens * (2 * h * p + 2 * g * n + h) * itemsize)


def grouped_dot_cost(cfg: dict, rows, itemsize: int = 2) -> list:
    """[(FLOP, bytes)] of the two grouped products of one expert layer
    in one program call whose held experts took ``rows`` (held,): in the
    latent, ``latent x width`` then ``width x latent``, no gate.  Each
    needs 2 FLOP a held row and matrix element, and moves at least the
    held rows of its row operands and the matrix of every expert that
    took a row, once."""
    lat, f = cfg["moe_latent_size"], cfg["moe_intermediate_size"]
    r = int(sum(int(x) for x in rows))
    touched = sum(1 for x in rows if int(x) > 0)
    return [(2 * r * k * n, itemsize * (r * k + r * n + touched * k * n))
            for k, n in ((lat, f), (f, lat))]


def kernel_calls(cfg: dict, steps: list, block_size: int) -> dict:
    """What ``readers/scope_roofline.py`` and ``kernel_roofline.py`` take
    for the traced phase's step records ``steps``.  A decode step that
    counted ``ssm_states_live`` is ONE call of the state update over
    that many (slot, layer) pairs; a step that admitted
    ``prefill_tokens`` prompt tokens is one call of the scan a Mamba-2
    layer over them (the cost is linear in the tokens, so a step that
    admitted two prompts counts their sum); every ``(program, rows)`` of
    a step's ``moe_rows`` is two grouped products an expert layer."""
    n_mamba = cfg["hybrid_override_pattern"].count("M")
    update, scan, grouped = [], [], []
    for r in steps:
        if r.get("active", 0) > 0 and r.get("ssm_states_live", 0) > 0:
            update.append(state_update_cost(cfg, r["ssm_states_live"]))
        if r.get("prefill_tokens", 0) > 0:
            scan += [scan_cost(cfg, r["prefill_tokens"])] * n_mamba
        for _, rows in r.get("moe_rows", ()):
            for layer in rows:
                grouped += grouped_dot_cost(cfg, layer)
    nested = nested_scopes()
    return {
        "ssm_state_update": {"scope": nested["ssm_state_update"],
                             "calls": update},
        "ssm_scan": {"scope": nested["ssm_scan"], "calls": scan},
        "moe_grouped_dot.serve": {
            "events": "ragged-dot-none", "calls": grouped,
            "beside": "ragged-dot-metadata"}}
