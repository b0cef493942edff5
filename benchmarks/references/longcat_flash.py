"""Plain float32 reference of the LongCat-Flash layer as one chip of a
32-chip group sees it: a published layer is a double block, two rotated
latent-attention sublayers with scaled latents, two dense swiglu FFNs,
and ONE expert layer that reads the stream after the first attention
and joins it after the second dense FFN (the shortcut-connected MoE),
routed by a softmax over the routed and the zero-compute experts.
Straightforward ``jax.numpy``: no kernel, no cache, no absorbed product,
no batching trick.  It imports nothing of the program.

``x`` the stream, ``N`` an rmsnorm with its own scale (eps 1e-5), no
bias anywhere::

    a1 = x  + MLA_0(N(x))                 m = N(a1)
    s  = MoE(m)                           # the shortcut branch, held for later
    h1 = a1 + FFN_0(m)                    # swiglu 6144 -> 12288 -> 6144, the same m
    a2 = h1 + MLA_1(N(h1))
    h2 = a2 + FFN_1(N(a2))
    out = h2 + s

    MLA(y):  cq = sqrt(d / q_rank) N(y Wqa);  q = cq Wq -> h x [nope | rope]
             [c ; k_r] = y Wa;  ckv = sqrt(d / kv_rank) N(c);  k_r as it is
             [k_n ; v]_h = ckv Wb;  rope on q's rope channels and on k_r
             o_h = softmax_causal(q_h . [k_n,h ; k_r] / sqrt(nope + rope)) v_h
             MLA = concat_h(o_h) Wo

    MoE(m):  p = softmax(m Wr) over the routed AND the zero-compute experts
             C = the top-k of p + b;  w_e = scale * p_e for e in C, not renormalised
             MoE = sum_{e in C, e held} w_e E_e(m) + (sum_{e in C, e zero-compute} w_e) m

What the experts held elsewhere would add is left out, as the program
leaves it out; the zero-compute experts' part is whole.  ``layers`` are
the program's leaves, one sublayer at a time (the first of a pair
carries ``branch``, the expert layer's leaves), from
``benchmarks/families/longcat_flash.py`` in the layout the configuration
file states, cast to float32 and multiplied at ``highest`` precision.
``mm="fp8"`` is the control of "How correct is decided"
(``references/dense_decoder.py`` has the recipe).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from benchmarks.references.dense_decoder import (F32, MATMULS, _static,
                                                 rms_norm, rope)
from benchmarks.references.openpangu_moe import (attention, head_logits,
                                                 swiglu)


def latent_scales(cfg) -> tuple:
    d = cfg["hidden_size"]
    return ((d / cfg["q_lora_rank"]) ** 0.5 if cfg["mla_scale_q_lora"]
            else 1.0,
            (d / cfg["kv_lora_rank"]) ** 0.5 if cfg["mla_scale_kv_lora"]
            else 1.0)


def mixer(cfg, p, y, positions, mm):
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    h = cfg["num_attention_heads"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    rank = cfg["kv_lora_rank"]
    q_scale, kv_scale = latent_scales(cfg)
    b, s, _ = y.shape
    cq = q_scale * rms_norm(mm(y, p["wqa"]), p["q_norm"]["scale"], eps)
    q = mm(cq, p["wq"]).reshape(b, s, h, dn + dr)
    q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], positions, theta)],
                        axis=-1)
    latent = mm(y, p["wa"])
    ckv = kv_scale * rms_norm(latent[..., :rank], p["kv_norm"]["scale"], eps)
    k_r = rope(latent[..., rank:][:, :, None, :], positions, theta)[:, :, 0]
    kv = mm(ckv, p["wb"]).reshape(b, s, h, dn + dv)
    return mm(attention(cfg, q, kv[..., :dn], k_r, kv[..., dn:], mm),
              p["wo"])


def routing(cfg, p, m, mm):
    """Per token the weight of every output of the router (0 where not
    chosen): softmax scores over routed and zero-compute experts, the
    top-k largest of score + bias, their scores scaled and NOT
    renormalised."""
    width = cfg["published"]["n_routed_experts"] + cfg["zero_expert_num"]
    prob = jax.nn.softmax(mm(m, p["router"]), axis=-1)
    _, chosen = jax.lax.top_k(prob + p["bias"].astype(F32), cfg["moe_topk"])
    w = cfg["routed_scaling_factor"] * jnp.take_along_axis(
        prob, chosen, axis=-1)
    return jnp.sum(jax.nn.one_hot(chosen, width, dtype=F32) * w[..., None],
                   axis=-2)


def moe(cfg, p, m, mm, first=None, held=None, zero=True):
    """The held experts' weighted outputs, one expert at a time over
    every token, plus (``zero``) the zero-compute experts' part."""
    first = cfg["deployment_share"]["first_expert"] if first is None \
        else first
    held = cfg["n_routed_experts"] if held is None else held
    weight = routing(cfg, p, m, mm)
    n = cfg["published"]["n_routed_experts"]
    start = jnp.sum(weight[..., n:], axis=-1, keepdims=True) * m if zero \
        else jnp.zeros_like(m)

    def add(y, expert):
        w1, w2, w_e = expert
        return y + w_e[..., None] * swiglu(m, w1, w2, mm), None

    y, _ = jax.lax.scan(add, start, (
        p["w1"], p["w2"],
        jnp.moveaxis(weight[..., first:first + held], -1, 0)))
    return y


def first_half(cfg, blk, x, positions, mm):
    """``(h1, s)``: the first attention and dense FFN of a published
    layer, and the shortcut branch held for later."""
    eps = cfg["rms_norm_eps"]
    a1 = x + mixer(cfg, blk["mixer"], rms_norm(x, blk["ln1"]["scale"], eps),
                   positions, mm)
    m = rms_norm(a1, blk["ln2"]["scale"], eps)
    s = moe(cfg, blk["branch"], m, mm)
    return a1 + swiglu(m, blk["w1"], blk["w2"], mm), s


def second_half(cfg, blk, h1, s, positions, mm):
    eps = cfg["rms_norm_eps"]
    a2 = h1 + mixer(cfg, blk["mixer"],
                    rms_norm(h1, blk["ln1"]["scale"], eps), positions, mm)
    h2 = a2 + swiglu(rms_norm(a2, blk["ln2"]["scale"], eps), blk["w1"],
                     blk["w2"], mm)
    return h2 + s


def layer(cfg, first, second, x, positions, mm):
    """One published layer from its two sublayers' leaves."""
    h1, s = first_half(cfg, first, x, positions, mm)
    return second_half(cfg, second, h1, s, positions, mm)


def forward(cfg, top, blocks, tokens, mm):
    """Logits at every position, the whole stack resident: small sizes
    (the tests differentiate it)."""
    x = top["embed"].astype(F32)[tokens]
    positions = jnp.arange(tokens.shape[1])
    for first, second in zip(blocks[0::2], blocks[1::2]):
        x = layer(cfg, first, second, x, positions, mm)
    return head_logits(cfg, top, x, mm)


# -------------------------------------------------------------- serving

def _plain(cfg: dict) -> tuple:
    """The configuration's plain values and the two nested groups the
    layer reads, as a hashable jit argument."""
    return _static(cfg) + (
        ("deployment_share", _static(cfg["deployment_share"])),
        ("published", _static(cfg["published"])))


def _unplain(key: tuple) -> dict:
    return {k: dict(v) if isinstance(v, tuple) else v for k, v in key}


@partial(jax.jit, static_argnames=("cfg", "mm"))
def _first_half(cfg, mm, blk, x):
    return first_half(_unplain(cfg), blk, x, jnp.arange(x.shape[1]),
                      MATMULS[mm])


@partial(jax.jit, static_argnames=("cfg", "mm"))
def _second_half(cfg, mm, blk, h1, s):
    return second_half(_unplain(cfg), blk, h1, s, jnp.arange(h1.shape[1]),
                       MATMULS[mm])


@partial(jax.jit, static_argnames=("cfg", "mm"))
def _head_at(cfg, mm, top, x, rows):
    xr = jnp.take_along_axis(x, rows[..., None], axis=1)
    return head_logits(_unplain(cfg), top, xr, MATMULS[mm])


def logits_at(cfg: dict, top, layers, tokens, rows, mm: str = "f32"):
    """Full forward over ``tokens`` (b, s), one sublayer at a time
    (``layers`` yields each sublayer's leaves in turn, the first of a
    published layer with its ``branch``, so the whole stack is never
    resident) and one sequence at a time, and the logits at positions
    ``rows`` (b, n): (b, n, vocab)."""
    key = _plain(cfg)
    x = top["embed"].astype(F32)[tokens]
    each = range(x.shape[0])
    layers = iter(layers)
    for first in layers:
        halves = [_first_half(key, mm, first, x[i:i + 1]) for i in each]
        del first
        second = next(layers)
        x = jnp.concatenate([_second_half(key, mm, second, *halves[i])
                             for i in each])
    return _head_at(key, mm, top, x, rows)
