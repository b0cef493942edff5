"""Plain float32 reference of the openPangu-Ultra-MoE layer as one chip
of a sixteen-chip group sees it: rotated latent attention with a low-rank
query, a norm before and after each branch ("sandwich"), a dense swiglu
FFN in the leading layers and, in the others, a shared expert plus the
held share of a sigmoid top-k expert layer.  Straightforward
``jax.numpy``: no kernel, no cache, no absorbed product, no batching
trick.  It imports nothing of the program.

``x`` the stream, ``N`` an rmsnorm with its own scale, ``h`` heads::

    a   = N_in(x)
    q   = N_q(a Wqa) Wqb                 -> h x (nope + rope);  q_r = rope(q[..., nope:], pos)
    [c ; k_r] = a Wkva                   c' = N_kv(c);  k_r' = rope(k_r, pos), shared by all heads
    [k_n ; v]_h = c' Wkvb
    o_h = softmax_causal((q_n,h . k_n,h + q_r,h . k_r') / sqrt(nope + rope)) v_h
    x   = x + N_post_attn(concat_h(o_h) Wo)
    m   = N_pre_mlp(x)
    F(m) = W2(silu(Wg m) * Wu m)                                   (dense layers)
    F(m) = E_shared(m) + sum_{e in top-k(s), e held} scale * s_e / sum_{top-k} s * E_e(m)
           with s = sigmoid(m Wr) over the router's full width     (expert layers)
    x   = x + N_post_mlp(F(m))
    logits = N_f(x) W_head

What the experts held elsewhere would add is left out, as the program
leaves it out.  The scores of attention are written out, one head at a
time (a head's scores of a 4,736-token sequence are 90 MB in float32);
the experts are a loop over the held ones, each over every token.
Weights come leaf by leaf from ``benchmarks/families/openpangu_moe.py``
in the layout the configuration file states, are cast to float32 and
multiplied at ``highest`` precision.  ``mm="fp8"`` is the control of "How
correct is decided" (``references/dense_decoder.py`` has the recipe).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from benchmarks.references.dense_decoder import (F32, MATMULS, _static,
                                                 rms_norm, rope)


def layer_is_dense(cfg: dict, index: int) -> bool:
    return index < cfg["first_k_dense_replace"]


def attention(cfg, q, k_n, k_r, v, mm):
    """Causal softmax attention with its scores written out, one head at
    a time.  ``q`` (b, s, h, nope + rope) with its rope part rotated,
    ``k_n`` (b, s, h, nope), ``k_r`` (b, s, rope) rotated and shared by
    the heads, ``v`` (b, s, h, dv)."""
    dn = cfg["qk_nope_head_dim"]
    b, s, h, dqk = q.shape
    keep = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    k_rt = jnp.swapaxes(k_r, -1, -2)                         # (b, rope, s)

    def one(args):
        q_h, k_h, v_h = args                                 # (b, s, .)
        sc = (mm(q_h[..., :dn], jnp.swapaxes(k_h, -1, -2))
              + mm(q_h[..., dn:], k_rt)) / jnp.sqrt(F32(dqk))
        sc = jnp.where(keep, sc, -jnp.inf)
        return mm(jax.nn.softmax(sc, axis=-1), v_h)

    heads = lambda t: jnp.moveaxis(t, 2, 0)                  # (h, b, s, .)
    o = jax.lax.map(one, (heads(q), heads(k_n), heads(v)))
    return jnp.moveaxis(o, 0, 2).reshape(b, s, -1)


def mixer(cfg, p, a, positions, mm):
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    h = cfg["num_attention_heads"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    rank = cfg["kv_lora_rank"]
    b, s, _ = a.shape
    q = mm(rms_norm(mm(a, p["wqa"]), p["q_norm"]["scale"], eps),
           p["wq"]).reshape(b, s, h, dn + dr)
    q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], positions, theta)],
                        axis=-1)
    latent = mm(a, p["wa"])
    c = rms_norm(latent[..., :rank], p["kv_norm"]["scale"], eps)
    k_r = rope(latent[..., rank:][:, :, None, :], positions, theta)[:, :, 0]
    kv = mm(c, p["wb"]).reshape(b, s, h, dn + dv)
    return mm(attention(cfg, q, kv[..., :dn], k_r, kv[..., dn:], mm),
              p["wo"])


def swiglu(x, w1, w2, mm):
    gate, up = jnp.split(mm(x, w1), 2, axis=-1)
    return mm(jax.nn.silu(gate) * up, w2)


def routing(cfg, p, m, mm):
    """Per token the weight of every expert of the router's full width
    (0 where not chosen): sigmoid scores, the top-k largest, their scores
    renormalised over the chosen and scaled."""
    width = cfg["published"]["n_routed_experts"]
    s = jax.nn.sigmoid(mm(m, p["router"]))
    _, chosen = jax.lax.top_k(s, cfg["num_experts_per_tok"])
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    w = cfg["routed_scaling_factor"] * picked \
        / jnp.sum(picked, axis=-1, keepdims=True)
    return jnp.sum(jax.nn.one_hot(chosen, width, dtype=F32) * w[..., None],
                   axis=-2)


def experts(cfg, p, m, mm, first=None, held=None):
    """The shared expert plus the held experts' weighted outputs, one
    expert at a time over every token."""
    first = cfg["deployment_share"]["first_expert"] if first is None \
        else first
    held = cfg["n_routed_experts"] if held is None else held
    weight = routing(cfg, p, m, mm)[..., first:first + held]

    def add(y, expert):
        w1, w2, w_e = expert
        return y + w_e[..., None] * swiglu(m, w1, w2, mm), None

    y, _ = jax.lax.scan(add, swiglu(m, p["shared_w1"], p["shared_w2"], mm),
                        (p["w1"], p["w2"], jnp.moveaxis(weight, -1, 0)))
    return y


def layer(cfg, blk, x, positions, mm):
    eps = cfg["rms_norm_eps"]
    a = rms_norm(x, blk["ln1"]["scale"], eps)
    x = x + rms_norm(mixer(cfg, blk["mixer"], a, positions, mm),
                     blk["ln1_post"]["scale"], eps)
    m = rms_norm(x, blk["ln2"]["scale"], eps)
    if "experts" in blk:
        f = experts(cfg, blk["experts"], m, mm)
    else:
        f = swiglu(m, blk["w1"], blk["w2"], mm)
    return x + rms_norm(f, blk["ln2_post"]["scale"], eps)


def head_logits(cfg, top, x, mm):
    return mm(rms_norm(x, top["ln_f"]["scale"], cfg["rms_norm_eps"]),
              top["unembed"])


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
def _layer_fwd(cfg, mm, blk, x):
    return layer(_unplain(cfg), blk, x, jnp.arange(x.shape[1]), MATMULS[mm])


@partial(jax.jit, static_argnames=("cfg", "mm"))
def _head_at(cfg, mm, top, x, rows):
    xr = jnp.take_along_axis(x, rows[..., None], axis=1)
    return head_logits(_unplain(cfg), top, xr, MATMULS[mm])


def logits_at(cfg: dict, top, layers, tokens, rows, mm: str = "f32"):
    """Full forward over ``tokens`` (b, s), one layer at a time
    (``layers`` yields each layer's leaves in turn, so the whole stack is
    never resident) and one sequence at a time, and the logits at
    positions ``rows`` (b, n): (b, n, vocab)."""
    key = _plain(cfg)
    x = top["embed"].astype(F32)[tokens]
    for blk in layers:
        x = jnp.concatenate([_layer_fwd(key, mm, blk, x[i:i + 1])
                             for i in range(x.shape[0])])
    return _head_at(key, mm, top, x, rows)
