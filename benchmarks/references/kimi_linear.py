"""Plain float32 reference of the Kimi-Linear decoder
(``model_type`` ``kimi_linear``): pre-norm residual blocks whose mixer
is either Kimi Delta Attention (KDA: a gated delta rule with a
per-channel decay) or latent attention without rotation (MLA, NoPE),
and whose FFN is either a dense swiglu or sigmoid-routed swiglu experts
with a shared expert; rmsnorm, untied head, mean next-token
cross-entropy.  Straightforward ``jax.numpy``: KDA is the recurrence
over time, MLA writes its scores out, the experts are a loop.

It imports nothing of the program.  It shares with
``dense_decoder.py`` only what is no part of either architecture: the
matrix product (``mm``: float32 at ``highest``, or the float8 control),
rmsnorm, the head's loss and the SGD rounding.  Weights come leaf by
leaf from ``benchmarks/families/kimi_linear.py`` in the layout the
configuration file states.

The equations (x the normed input of a layer, per head h):

* KDA: ``q = l2norm(silu(conv4(x Wq)))``, ``k`` likewise, ``v =
  silu(conv4(x Wv))``; ``conv4`` is a causal depthwise convolution of 4
  taps, zero history; ``g_t = -exp(A_h) softplus(Wf2 (Wf1 x_t) + b_dt)``
  per channel, ``beta_t = sigmoid(x_t Wb)``; ``S_t = (I - beta_t k_t
  k_t^T) diag(exp g_t) S_{t-1} + beta_t k_t v_t^T``, ``S_0 = 0``; ``o_t =
  S_t^T q_t / sqrt(d_k)``; ``y = Wo [rmsnorm_head(o) * sigmoid(Wg2 (Wg1
  x))]``.
* MLA: ``q = x Wq`` (h x 192); ``[c ; k_r] = x Wa``; ``[k_n ; v]_h =
  rmsnorm(c) Wb``; ``k_h = [k_n,h ; k_r]``, no rotation; causal
  ``softmax(q k^T / sqrt(192)) v``; ``y = Wo concat(o)``.
* experts: ``s = sigmoid(x Wr)`` over all experts; the k largest of ``s +
  b`` chosen; ``w_e = scale * s_e / sum over the chosen of s``; ``y = sum
  over chosen and held e of w_e E_e(x) + E_shared(x)``, ``E(x) = Wd
  (silu(Wg x) * Wu x)``.  Only the experts ``first`` ... ``first + held -
  1`` are held; what the others would add is left out.

Memory is bounded, never the arithmetic: the per-token states of one
8,192-token sequence would be 17 GB, so the scan over time is nested,
blocks of tokens under ``jax.checkpoint``; MLA loops over heads; the
step walks the chain rule back one layer at a time with ``jax.vjp``.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from benchmarks.references.dense_decoder import (
    F32, MATMULS, _round, _static, head_loss, rms_norm)

_TIME_BLOCK = 128


class Plan(NamedTuple):
    """The configuration's plain numbers, hashable."""
    eps: float
    heads: int
    kda_dim: int
    kv_rank: int
    qk_nope: int
    qk_rope: int
    v_dim: int
    router_width: int
    top_k: int
    scale: float
    first: int
    held: int
    kinds: tuple          # per layer ("kda" | "mla", "dense" | "experts")


def plan(cfg: dict) -> Plan:
    lin = cfg["linear_attn_config"]
    n = cfg["num_hidden_layers"]
    kinds = tuple(
        ("kda" if i + 1 in lin["kda_layers"] else "mla",
         "dense" if i < cfg["first_k_dense_replace"] else "experts")
        for i in range(n))
    return Plan(cfg["rms_norm_eps"], cfg["num_attention_heads"],
                lin["head_dim"], cfg["kv_lora_rank"],
                cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                cfg["v_head_dim"], cfg["published"]["num_experts"],
                cfg["num_experts_per_token"], cfg["routed_scaling_factor"],
                cfg["deployment_share"]["first_expert"], cfg["num_experts"],
                kinds)


# ------------------------------------------------------------------- KDA

def conv4(x, w):
    """Causal depthwise convolution: ``out_t = sum_j w[j] x_{t-3+j}``."""
    taps, s = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(xp[:, j:j + s] * w[j].astype(F32) for j in range(taps))


def l2norm(x):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def delta_rule(q, k, v, g, beta):
    """The recurrence over time.  q, k, g (b, s, h, dk); v (b, s, h, dv);
    beta (b, s, h)."""
    b, s, h, dk = q.shape
    block = min(_TIME_BLOCK, s)
    pad = -s % block
    # A padded token writes nothing and decays nothing.
    seq = lambda x: jnp.moveaxis(
        jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2)), 1, 0
    ).reshape((s + pad) // block, block, *x.shape[:1], *x.shape[2:])

    def token(S, inp):
        q_t, k_t, v_t, g_t, b_t = inp
        S = S * jnp.exp(g_t)[..., None]
        seen = jnp.sum(k_t[..., None] * S, axis=-2)
        S = S + (b_t[..., None] * k_t)[..., None] * (v_t - seen)[..., None, :]
        return S, jnp.sum(q_t[..., None] * S, axis=-2)

    @jax.checkpoint
    def tokens(S, inp):
        return jax.lax.scan(token, S, inp)

    S0 = jnp.zeros((b, h, dk, v.shape[-1]), F32)
    _, o = jax.lax.scan(tokens, S0, (seq(q), seq(k), seq(v), seq(g),
                                     seq(beta)))
    o = o.reshape(s + pad, b, h, -1)[:s]
    return jnp.moveaxis(o, 0, 1) / jnp.sqrt(F32(dk))


def kda(pl: Plan, p, x, mm):
    b, s, _ = x.shape
    h, hd = pl.heads, pl.kda_dim
    heads = lambda t: t.reshape(b, s, h, hd)
    q, k, v = map(heads, jnp.split(
        jax.nn.silu(conv4(mm(x, p["wqkv"]), p["conv"])), 3, axis=-1))
    rate = jax.nn.softplus(mm(mm(x, p["wf1"]), p["wf2"])
                           + p["dt_bias"].astype(F32))
    g = -jnp.exp(p["a_log"].astype(F32))[:, None] * heads(rate)
    beta = jax.nn.sigmoid(mm(x, p["wb"]))
    o = delta_rule(l2norm(q), l2norm(k), v, g, beta)
    gate = jax.nn.sigmoid(heads(mm(mm(x, p["wg1"]), p["wg2"])))
    o = rms_norm(o, p["norm"]["scale"], pl.eps) * gate
    return mm(o.reshape(b, s, h * hd), p["wo"])


# ------------------------------------------------------------------- MLA

def attention(q, k, v, mm):
    """Causal softmax attention, one head at a time, scores written out.
    q, k (b, s, h, dqk); v (b, s, h, dv)."""
    b, s, h, dqk = q.shape
    keep = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]

    @jax.checkpoint
    def one(args):
        qh, kh, vh = args                                   # (b, s, d)
        sc = mm(qh, jnp.swapaxes(kh, -1, -2)) / jnp.sqrt(F32(dqk))
        return mm(jax.nn.softmax(jnp.where(keep, sc, -jnp.inf), axis=-1), vh)

    o = jax.lax.map(one, tuple(jnp.moveaxis(t, 2, 0) for t in (q, k, v)))
    return jnp.moveaxis(o, 0, 2).reshape(b, s, -1)


def mla(pl: Plan, p, x, mm):
    b, s, _ = x.shape
    h, dn, dr, dv = pl.heads, pl.qk_nope, pl.qk_rope, pl.v_dim
    q = mm(x, p["wq"]).reshape(b, s, h, dn + dr)
    latent = mm(x, p["wa"])
    c, k_shared = latent[..., :pl.kv_rank], latent[..., pl.kv_rank:]
    kv = mm(rms_norm(c, p["kv_norm"]["scale"], pl.eps),
            p["wb"]).reshape(b, s, h, dn + dv)
    k = jnp.concatenate(
        [kv[..., :dn],
         jnp.broadcast_to(k_shared[:, :, None, :], (b, s, h, dr))], axis=-1)
    return mm(attention(q, k, kv[..., dn:], mm), p["wo"])


# --------------------------------------------------------------- experts

def swiglu(x, w1, w2, mm):
    gate, up = jnp.split(mm(x, w1), 2, axis=-1)
    return mm(jax.nn.silu(gate) * up, w2)


def routing(pl: Plan, p, x, mm):
    """Per token the weight of every expert (0 where not chosen)."""
    s = jax.nn.sigmoid(mm(x, p["router"]))
    _, chosen = jax.lax.top_k(
        s + jax.lax.stop_gradient(p["bias"].astype(F32)), pl.top_k)
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    w = pl.scale * picked / jnp.sum(picked, axis=-1, keepdims=True)
    return jnp.sum(jax.nn.one_hot(chosen, pl.router_width, dtype=F32)
                   * w[..., None], axis=-2)


def experts(pl: Plan, p, x, mm, first=None, held=None):
    """The held experts' weighted outputs, one expert at a time over
    every token, plus the shared expert.  (Each expert's pass is
    rematerialised on the way back, or 32 experts' intermediates of
    16,384 tokens would be kept at once.)"""
    first = pl.first if first is None else first
    held = pl.held if held is None else held
    weight = routing(pl, p, x, mm)[..., first:first + held]

    @jax.checkpoint
    def one(x, w1, w2, w_e):
        return w_e[..., None] * swiglu(x, w1, w2, mm)

    def add(y, expert):
        return y + one(x, *expert), None

    y, _ = jax.lax.scan(add, jnp.zeros_like(x),
                        (p["w1"], p["w2"], jnp.moveaxis(weight, -1, 0)))
    if "shared_w1" in p:
        y = y + swiglu(x, p["shared_w1"], p["shared_w2"], mm)
    return y


# ----------------------------------------------------------------- layer

def layer(pl: Plan, kind: tuple, blk, x, mm):
    """One block of kind ``(mixer, ffn)``."""
    mixer, ffn = kind
    y = rms_norm(x, blk["ln1"]["scale"], pl.eps)
    x = x + (kda if mixer == "kda" else mla)(pl, blk["mixer"], y, mm)
    y = rms_norm(x, blk["ln2"]["scale"], pl.eps)
    if ffn == "experts":
        return x + experts(pl, blk["experts"], y, mm)
    return x + swiglu(y, blk["w1"], blk["w2"], mm)


# -------------------------------------------------------------- training

@partial(jax.jit, static_argnames=("pl", "kind", "mm"))
def _layer_fwd(pl, kind, mm, blk, x):
    return layer(pl, kind, blk, x, MATMULS[mm])


def _norms(grads):
    return jax.tree.map(lambda g: jnp.linalg.norm(g.ravel()), grads)


def _apply(leaves, grads, lr):
    return jax.tree.map(lambda p, g: _round(p, g, lr, p.dtype), leaves, grads)


@partial(jax.jit, static_argnames=("pl", "kind", "mm", "lr"),
         donate_argnums=(5,))
def _layer_bwd(pl, kind, mm, lr, blk, gx, x):
    blk32 = jax.tree.map(lambda a: a.astype(F32), blk)
    _, vjp = jax.vjp(lambda b_, x_: layer(pl, kind, b_, x_, MATMULS[mm]),
                     blk32, x)
    gblk, gx = vjp(gx)
    return _apply(blk, gblk, lr), _norms(gblk), gx


@partial(jax.jit, static_argnames=("cfg", "mm", "lr"))
def _head_step(cfg, mm, lr, head, x, tokens):
    head32 = jax.tree.map(lambda a: a.astype(F32), head)
    loss, (ghead, gx) = jax.value_and_grad(
        lambda h_, x_: head_loss(dict(cfg), h_, x_, tokens, MATMULS[mm]),
        argnums=(0, 1))(head32, x)
    return loss, _apply(head, ghead, lr), _norms(ghead), gx


@partial(jax.jit, static_argnames=("lr",))
def _embed_step(lr, embed, tokens, gx):
    g = jnp.zeros(embed.shape, F32).at[tokens].add(gx)
    return _round(embed, g, lr, embed.dtype), jnp.linalg.norm(g.ravel())


def step(cfg: dict, params, tokens, lr: float, mm: str = "f32"):
    """One SGD step of the reference on a parameter tree in the
    configuration's dtype; returns ``(loss, new_params, grad_norms)``,
    the last a tree like the parameters with each leaf's float32
    gradient norm.  Layer by layer: forward keeps the layer boundaries,
    backward re-runs one layer under ``jax.vjp`` and rounds its new
    leaves at once."""
    pl, key = plan(cfg), _static(cfg)
    blocks = params["blocks"]
    with jax.default_matmul_precision("highest"):
        xs = [params["embed"].astype(F32)[tokens]]
        for kind, blk in zip(pl.kinds, blocks):
            xs.append(_layer_fwd(pl, kind, mm, blk, xs[-1]))
        head = {"ln_f": params["ln_f"], "unembed": params["unembed"]}
        loss, new, norms, gx = _head_step(key, mm, lr, head, xs.pop(), tokens)
        new["blocks"], norms["blocks"] = ([None] * len(blocks) for _ in "ab")
        for i in reversed(range(len(blocks))):
            new["blocks"][i], norms["blocks"][i], gx = _layer_bwd(
                pl, pl.kinds[i], mm, lr, blocks[i], gx, xs.pop())
        new["embed"], norms["embed"] = _embed_step(lr, params["embed"],
                                                   tokens, gx)
    return loss, new, norms


def sgd_step(cfg: dict, params, tokens, lr: float, mm: str = "f32"):
    """``(loss, new_params)``: the same entry as
    ``dense_decoder.sgd_step``."""
    return step(cfg, params, tokens, lr, mm)[:2]
