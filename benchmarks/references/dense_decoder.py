"""Plain float32 reference of a dense pre-norm decoder (rmsnorm, rope,
grouped-query causal attention with an optional sliding window, swiglu,
untied output head): Mistral-7B-v0.1 and InternLM2 as published, in
straightforward ``jax.numpy``, no kernels, no cache, no batching tricks.

It imports nothing of the program.  It takes weights leaf by leaf from
``benchmarks/weights.py`` in the layout the configuration file states
(``wqkv`` = [q | k | v] columns, ``w1`` = [gate | up]; a departure from
the checkpoints' layouts that changes no arithmetic), casts them to
float32 and multiplies at ``highest`` precision.  Memory is bounded by
working layer by layer: the training reference keeps only the layer
boundaries and walks the chain rule back one layer at a time with
``jax.vjp``; attention loops over KV heads.

``mm`` is the matrix multiplication.  ``matmul_f32`` is the reference;
``matmul_fp8`` is the control of "How correct is decided": the same
mathematics with every product's operands rounded to float8 (e4m3
forward, e5m2 for the cotangents, each scaled to its tensor's largest
value), the nearest precision below bfloat16.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST


def matmul_f32(x, w):
    return jnp.matmul(x.astype(F32), w.astype(F32), precision=HIGHEST)


def _round_fp8(x, dtype, top):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / s).astype(dtype).astype(F32) * s


def _fp8(x):
    """An operand rounded to float8 e4m3 at the tensor's own scale.  The
    derivative is that of the identity (the rounding is piecewise
    constant), so the backward products see the rounded operands."""
    x = x.astype(F32)
    return x + jax.lax.stop_gradient(
        _round_fp8(x, jnp.float8_e4m3fn, 448.0) - x)


@jax.custom_vjp
def _fp8_cotangent(y):
    """Identity whose cotangent is rounded to float8 e5m2 at its own
    scale: the backward products' other operand."""
    return y


_fp8_cotangent.defvjp(
    lambda y: (y, None),
    lambda _, g: (_round_fp8(g, jnp.float8_e5m2, 57344.0),))


def matmul_fp8(x, w):
    """Every product in float8, forward (e4m3 operands) and backward
    (e5m2 cotangents), accumulated in float32: the usual float8 recipe."""
    return _fp8_cotangent(jnp.matmul(_fp8(x), _fp8(w), precision=HIGHEST))


MATMULS = {"f32": matmul_f32, "fp8": matmul_fp8}


def rms_norm(x, scale, eps):
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x / jnp.sqrt(ms + eps) * scale.astype(F32)


def rope(x, positions, theta):
    """Half-split rotary embedding: channel i pairs with i + hd/2."""
    hd = x.shape[-1]
    half = hd // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) * 2.0 / hd)
    ang = positions.astype(F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(q, k, v, window, mm):
    """Causal softmax attention, one KV head (its group of query heads)
    at a time.  q (b, s, h, hd); k, v (b, s, h_kv, hd)."""
    b, s, h, hd = q.shape
    h_kv = k.shape[2]
    g = h // h_kv
    i = jnp.arange(s)[:, None]
    j = jnp.arange(s)[None, :]
    keep = j <= i
    if window:
        keep = keep & (i - j < window)
    qg = q.reshape(b, s, h_kv, g, hd).transpose(2, 0, 3, 1, 4)  # kv b g s hd
    kg = k.transpose(2, 0, 1, 3)                                 # kv b s hd
    vg = v.transpose(2, 0, 1, 3)

    @jax.checkpoint
    def one(args):
        qh, kh, vh = args
        sc = mm(qh, jnp.swapaxes(kh, -1, -2)[:, None]) / jnp.sqrt(F32(hd))
        sc = jnp.where(keep, sc, -jnp.inf)
        return mm(jax.nn.softmax(sc, axis=-1), vh[:, None])     # b g s hd

    o = jax.lax.map(one, (qg, kg, vg))                           # kv b g s hd
    return o.transpose(1, 3, 0, 2, 4).reshape(b, s, h * hd)


def layer(cfg, blk, x, positions, mm):
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    h, h_kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    b, s, d = x.shape
    hd = d // h
    qkv = mm(rms_norm(x, blk["ln1"]["scale"], eps), blk["wqkv"])
    q = rope(qkv[..., :h * hd].reshape(b, s, h, hd), positions, theta)
    k = rope(qkv[..., h * hd:(h + h_kv) * hd].reshape(b, s, h_kv, hd),
             positions, theta)
    v = qkv[..., (h + h_kv) * hd:].reshape(b, s, h_kv, hd)
    window = cfg.get("sliding_window") or 0
    x = x + mm(attention(q, k, v, window, mm), blk["wo"])
    gate, up = jnp.split(mm(rms_norm(x, blk["ln2"]["scale"], eps),
                            blk["w1"]), 2, axis=-1)
    return x + mm(jax.nn.silu(gate) * up, blk["w2"])


def head_logits(cfg, top, x, mm):
    return mm(rms_norm(x, top["ln_f"]["scale"], cfg["rms_norm_eps"]),
              top["unembed"])


def head_loss(cfg, top, x, tokens, mm):
    """Mean next-token cross entropy over all but each row's last
    position."""
    logp = jax.nn.log_softmax(head_logits(cfg, top, x[:, :-1], mm), axis=-1)
    ce = -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(ce)


# ------------------------------------------------------------- training

def _round(p, g, lr, dtype):
    """SGD in the configuration's parameter type: one rounding."""
    return (p.astype(F32) - lr * g).astype(dtype)


@partial(jax.jit, static_argnames=("cfg", "mm"))
def _layer_fwd(cfg, mm, blk, x):
    return layer(dict(cfg), blk, x, jnp.arange(x.shape[1]), MATMULS[mm])


@partial(jax.jit, static_argnames=("cfg", "mm", "lr"), donate_argnums=(4,))
def _layer_bwd(cfg, mm, lr, blk, gx, x):
    f = lambda b_, x_: layer(dict(cfg), b_, x_, jnp.arange(x.shape[1]),
                             MATMULS[mm])
    blk32 = jax.tree.map(lambda a: a.astype(F32), blk)
    _, vjp = jax.vjp(f, blk32, x)
    gblk, gx = vjp(gx)
    new = jax.tree.map(lambda p, g: _round(p, g, lr, p.dtype), blk, gblk)
    return new, gx


@partial(jax.jit, static_argnames=("cfg", "mm", "lr"))
def _head_step(cfg, mm, lr, head, x, tokens):
    head32 = jax.tree.map(lambda a: a.astype(F32), head)
    loss, (ghead, gx) = jax.value_and_grad(
        lambda h_, x_: head_loss(dict(cfg), h_, x_, tokens, MATMULS[mm]),
        argnums=(0, 1))(head32, x)
    new = jax.tree.map(lambda p, g: _round(p, g, lr, p.dtype), head, ghead)
    return loss, new, gx


@partial(jax.jit, static_argnames=("lr",))
def _embed_step(lr, embed, tokens, gx):
    g = jnp.zeros(embed.shape, F32).at[tokens].add(gx)
    return _round(embed, g, lr, embed.dtype)


def _static(cfg: dict) -> tuple:
    """The configuration's plain values as a hashable jit argument."""
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str, bool, type(None)))))


def sgd_step(cfg: dict, params, tokens, lr: float, mm: str = "f32"):
    """One SGD step of the reference on a parameter tree in the
    configuration's dtype; returns ``(loss, new_params)``.  Layer by
    layer: forward keeps the layer boundaries, backward re-runs one layer
    under ``jax.vjp`` and rounds its new leaves at once."""
    key = _static(cfg)
    xs = [params["embed"].astype(F32)[tokens]]
    for blk in params["blocks"]:
        xs.append(_layer_fwd(key, mm, blk, xs[-1]))
    head = {"ln_f": params["ln_f"], "unembed": params["unembed"]}
    loss, new, gx = _head_step(key, mm, lr, head, xs.pop(), tokens)
    blocks = []
    for blk in reversed(params["blocks"]):
        nb, gx = _layer_bwd(key, mm, lr, blk, gx, xs.pop())
        blocks.append(nb)
    new["blocks"] = blocks[::-1]
    new["embed"] = _embed_step(lr, params["embed"], tokens, gx)
    return loss, new


# -------------------------------------------------------------- serving

@partial(jax.jit, static_argnames=("cfg", "mm"))
def _head_at(cfg, mm, top, x, rows):
    """Logits at the listed positions of each sequence: (b, n, vocab)."""
    xr = jnp.take_along_axis(x, rows[..., None], axis=1)
    return head_logits(dict(cfg), top, xr, MATMULS[mm])


def logits_at(cfg: dict, top, layers, tokens, rows, mm: str = "f32"):
    """Full forward over ``tokens`` (b, s), one layer at a time
    (``layers`` yields each layer's leaves in turn, so the whole stack is
    never resident), and the logits at positions ``rows`` (b, n)."""
    key = _static(cfg)
    x = top["embed"].astype(F32)[tokens]
    for blk in layers:
        x = _layer_fwd(key, mm, blk, x)
    return _head_at(key, mm, top, x, rows)
