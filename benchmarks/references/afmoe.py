"""Plain float32 reference of the ``afmoe`` layer stack (Trinity-Mini):
gated grouped-query attention with normed queries and keys, sliding
(rotated, a window of positions) on three layers in four and full (no
rotation, every position before) on the fourth, a norm before and after
each branch, a dense swiglu FFN in the leading layers and, in the
others, a shared expert plus a sigmoid top-k expert layer of which this
chip holds every expert.  Straightforward ``jax.numpy``: no kernel, no
cache, no pages, no batching trick.  It imports nothing of the program.

``x`` the stream, ``N`` an rmsnorm with its own scale, ``h`` query
heads of ``hd`` channels on ``h_kv`` key-value heads::

    x0  = sqrt(hidden) E[token]                                  (mup_enabled)
    a   = N1(x)
    [q | k | v | g] = a Wqkv          q, g: h x hd;  k, v: h_kv x hd
    q   = gamma_q * rmsnorm_hd(q);  k = gamma_k * rmsnorm_hd(k)   head by head
    sliding layer:  q, k = rope(q, pos), rope(k, pos)  (theta, all hd channels)
                    position i reads j <= i with i - j < sliding_window
    full layer:     no rotation; position i reads every j <= i
    o_h = softmax(q_h . k_{h // (h / h_kv)} / sqrt(hd)) v_{h // (h / h_kv)}
    x   = x + N1'((o * sigmoid(g)) Wo)
    m   = N2(x)
    F(m) = W2(silu(Wg m) * Wu m)                                   (dense layers)
    F(m) = S(m) + sum_{e in top-k(s + b)} route_scale * s_e / sum_{top-k} s * E_e(m)
           with s = sigmoid(m Wr) in float32                       (expert layers)
    x   = x + N2'(F(m))
    logits = N_f(x) W_head

The window is a mask over the full score matrix, which is written out a
block of queries at a time (a block of 256 queries of a 17,408-token
sequence against all its keys is 0.57 GB in float32 over the four
key-value heads); the experts are a loop over the held ones, each over
every token.  ``first`` / ``held`` cut the expert layer to a share of
its experts (the test that two half shares add up to the whole, the
shared expert counted once); the cell holds them all.  Weights come leaf
by leaf from ``benchmarks/families/afmoe.py`` in the layout the
configuration file states, are cast to float32 and multiplied at
``highest`` precision.  ``mm="fp8"`` is the control of "How correct is
decided" (``references/dense_decoder.py`` has the recipe).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from benchmarks.references.dense_decoder import (F32, MATMULS, _static,
                                                 rms_norm, rope)

# Queries a block of the written-out scores holds.
QUERY_BLOCK = 256


def layer_is_dense(cfg: dict, index: int) -> bool:
    return index < cfg["num_dense_layers"]


def layer_is_sliding(cfg: dict, index: int) -> bool:
    kind = cfg["layer_types"][index]
    if kind not in ("sliding_attention", "full_attention"):
        raise ValueError(f"layer_types[{index}] = {kind!r}")
    return kind == "sliding_attention"


def attention(q, k, v, window: int, mm):
    """Causal softmax attention of one sequence with its scores written
    out, a block of queries at a time: ``q`` (s, h, hd), ``k`` and ``v``
    (s, h_kv, hd); ``window > 0`` keeps of a query's positions its own
    and the ``window - 1`` before it."""
    s, h, hd = q.shape
    h_kv = k.shape[1]
    g = h // h_kv
    blk = min(QUERY_BLOCK, s)
    n = -(-s // blk)
    qp = jnp.pad(q, ((0, n * blk - s), (0, 0), (0, 0)))
    kt = jnp.transpose(k, (1, 2, 0))                        # (h_kv, hd, s)
    vt = jnp.transpose(v, (1, 0, 2))                        # (h_kv, s, hd)
    j = jnp.arange(s)[None, :]

    def block(b):
        qb = jax.lax.dynamic_slice_in_dim(qp, b * blk, blk, 0)
        i = b * blk + jnp.arange(blk)[:, None]
        keep = j <= i
        if window:
            keep = keep & (i - j < window)
        # (h_kv, g * blk, hd): row r of a group is query r % blk of the
        # group's head r // blk.
        qg = qb.reshape(blk, h_kv, g, hd).transpose(1, 2, 0, 3).reshape(
            h_kv, g * blk, hd)
        sc = mm(qg, kt) / jnp.sqrt(F32(hd))
        sc = jnp.where(jnp.tile(keep, (g, 1))[None], sc, -jnp.inf)
        o = mm(jax.nn.softmax(sc, axis=-1), vt)            # (h_kv, g blk, hd)
        return o.reshape(h_kv, g, blk, hd).transpose(2, 0, 1, 3).reshape(
            blk, h, hd)

    o = jax.lax.map(block, jnp.arange(n))
    return o.reshape(n * blk, h, hd)[:s]


def mixer(cfg, p, a, sliding: bool, mm):
    """The attention branch of one sequence ``a`` (s, d), normed."""
    eps = cfg["rms_norm_eps"]
    h, h_kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        cfg["head_dim"]
    s = a.shape[0]
    proj = mm(a, p["wqkv"])
    q = proj[:, :h * hd].reshape(s, h, hd)
    k = proj[:, h * hd:(h + h_kv) * hd].reshape(s, h_kv, hd)
    v = proj[:, (h + h_kv) * hd:(h + 2 * h_kv) * hd].reshape(s, h_kv, hd)
    g = proj[:, (h + 2 * h_kv) * hd:]
    q = rms_norm(q, p["q_norm"]["scale"], eps)
    k = rms_norm(k, p["k_norm"]["scale"], eps)
    if sliding:
        positions = jnp.arange(s)
        q = rope(q[None], positions, cfg["rope_theta"])[0]
        k = rope(k[None], positions, cfg["rope_theta"])[0]
    o = attention(q, k, v, cfg["sliding_window"] if sliding else 0, mm)
    return mm(o.reshape(s, h * hd) * jax.nn.sigmoid(g), p["wo"])


def swiglu(x, w1, w2, mm):
    gate, up = jnp.split(mm(x, w1), 2, axis=-1)
    return mm(jax.nn.silu(gate) * up, w2)


def routing(cfg, p, m, mm):
    """Per token the weight of every expert (0 where not chosen):
    sigmoid scores, the top-k largest of score + selection bias, the
    chosen scores renormalised over the chosen and scaled."""
    score = jax.nn.sigmoid(mm(m, p["router"]))
    _, chosen = jax.lax.top_k(score + p["bias"].astype(F32),
                              cfg["num_experts_per_tok"])
    picked = jnp.take_along_axis(score, chosen, axis=-1)
    if cfg["route_norm"]:
        picked = picked / jnp.sum(picked, axis=-1, keepdims=True)
    w = cfg["route_scale"] * picked
    return jnp.sum(jax.nn.one_hot(chosen, cfg["num_experts"], dtype=F32)
                   * w[..., None], axis=-2)


def experts(cfg, p, m, mm, first: int = 0, held=None, shared: bool = True):
    """The shared expert plus the weighted outputs of the experts
    ``first`` to ``first + held - 1`` (all of them where not said), one
    expert at a time over every token; ``p["w1"]`` and ``p["w2"]`` hold
    those experts' matrices."""
    held = cfg["num_experts"] if held is None else held
    weight = routing(cfg, p, m, mm)[..., first:first + held]

    def add(y, expert):
        w1, w2, w_e = expert
        return y + w_e[..., None] * swiglu(m, w1, w2, mm), None

    y = swiglu(m, p["shared_w1"], p["shared_w2"], mm) if shared \
        else jnp.zeros_like(m)
    y, _ = jax.lax.scan(add, y, (p["w1"], p["w2"],
                                 jnp.moveaxis(weight, -1, 0)))
    return y


def layer(cfg, blk, x, sliding: bool, mm):
    """One layer on one sequence ``x`` (s, d)."""
    eps = cfg["rms_norm_eps"]
    a = rms_norm(x, blk["ln1"]["scale"], eps)
    x = x + rms_norm(mixer(cfg, blk["mixer"], a, sliding, mm),
                     blk["ln1_post"]["scale"], eps)
    m = rms_norm(x, blk["ln2"]["scale"], eps)
    if "experts" in blk:
        f = experts(cfg, blk["experts"], m, mm)
    else:
        f = swiglu(m, blk["w1"], blk["w2"], mm)
    return x + rms_norm(f, blk["ln2_post"]["scale"], eps)


def embed(cfg, top, tokens):
    x = top["embed"].astype(F32)[tokens]
    return x * jnp.sqrt(F32(cfg["hidden_size"])) if cfg["mup_enabled"] else x


def head_logits(cfg, top, x, mm):
    return mm(rms_norm(x, top["ln_f"]["scale"], cfg["rms_norm_eps"]),
              top["unembed"])


# -------------------------------------------------------------- serving

@partial(jax.jit, static_argnames=("cfg", "mm", "sliding"))
def _layer_fwd(cfg, mm, sliding, blk, x):
    return layer(dict(cfg), blk, x, sliding, MATMULS[mm])


@partial(jax.jit, static_argnames=("cfg", "mm"))
def _head_at(cfg, mm, top, x, rows):
    xr = jnp.take_along_axis(x, rows[..., None], axis=1)
    return head_logits(dict(cfg), top, xr, MATMULS[mm])


def forward(cfg: dict, layers, x, mm: str = "f32"):
    """The stack over the embedded rows ``x`` (b, s, d), a layer at a
    time (``layers`` yields each layer's leaves in turn) and a sequence
    at a time."""
    key = _static(cfg)
    with jax.default_matmul_precision("highest"):
        for index, blk in enumerate(layers):
            # One layer's leaves at a time: without the wait the host
            # runs ahead and the device holds every queued layer's.
            x = jax.block_until_ready(jnp.stack(
                [_layer_fwd(key, mm, layer_is_sliding(cfg, index), blk, x[i])
                 for i in range(x.shape[0])]))
    return x


def logits_at(cfg: dict, top, layers, tokens, rows, mm: str = "f32"):
    """Full forward over ``tokens`` (b, s), one layer and one sequence
    at a time, and the logits at positions ``rows`` (b, n): (b, n,
    vocab)."""
    x = forward(cfg, layers, embed(cfg, top, tokens), mm)
    with jax.default_matmul_precision("highest"):
        return _head_at(_static(cfg), mm, top, x, rows)
