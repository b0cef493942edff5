"""Plain float32 reference of the GLM-5.2 layer (``glm_moe_dsa``) as one
chip of a sixteen-chip group sees it: rotated latent attention with a
low-rank query that attends, for every query, only the positions a
learned indexer selects; the selection made by the layers whose
``indexer_types`` entry is ``"full"`` and reused as it is by the
``"shared"`` layers above them; a dense swiglu FFN in the leading layers
and, in the others, a shared expert plus the held share of a sigmoid
top-k expert layer.  Straightforward ``jax.numpy``: no kernel, no cache,
no absorbed product, no batching trick.  It imports nothing of the
program.

``x`` the stream, ``N`` an rmsnorm with its own scale (eps 1e-5), no
bias but the index key's layer-norm, ``t`` a query position, ``s <= t``
a cached one::

    y   = N(x)
    cq  = N(y Wqa);  q = cq Wq -> h x [nope | rope];  [c ; kr] = y Wa;  ckv = N(c)
    [k_n ; v]_h = ckv Wb;  rope (theta) on q's rope channels and on kr

    indexer, on a "full" layer:
    qI_{t,j} = (cq_t Wq_i)_j          j = 1..n_I, the first ``rope`` channels rotated by t
    kI_s     = LN(y_s Wk_i)           scale and bias, the first ``rope`` channels rotated by s
    w_{t,j}  = (y_t Ww_i)_j * n_I^-1/2 * d_I^-1/2
    I_{t,s}  = sum_j w_{t,j} relu(qI_{t,j} . kI_s)
    S_t      = the index_topk positions s <= t of the largest I_{t,s} (all of them
               while t < index_topk); equal scores to the earlier position
    on a "shared" layer: S_t of the nearest "full" layer below

    o_h = softmax over s in S_t of (q_h . [k_n,h ; kr]_s / sqrt(nope + rope)) v_h
    a   = x + concat_h(o_h) Wo
    m   = N(a)
    F(m) = W2(silu(Wg m) * Wu m)                                   (dense layers)
    F(m) = E_shared(m) + sum_{e in C, e held} scale * p_e / sum_C p * E_e(m)
           p = sigmoid(m Wr), C the top-k of p + b over the router's full width
    out = a + F(m)
    logits = N_f(x) W_head

What the experts held elsewhere would add is left out, as the program
leaves it out.  One sequence, a layer at a time, and inside a layer a
block of queries at a time: the index scores of a block are ``(block,
s)`` float32 and the attention scores ``(heads, block, s)``, so 17,408
positions fit; the selection is kept as ``(s, index_topk)`` positions
between the layers that share it.  Weights come leaf by leaf from
``benchmarks/families/glm_dsa.py`` in the layout the configuration file
states, are cast to float32 and multiplied at ``highest`` precision.
``mm="fp8"`` is the control of "How correct is decided"
(``references/dense_decoder.py`` has the recipe); the index keys are
rounded to nothing here (the published serving code keeps them in
float8, the program in bfloat16: the configuration file says so).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from benchmarks.references.dense_decoder import (F32, MATMULS, _static,
                                                 rms_norm, rope)
from benchmarks.references.openpangu_moe import head_logits, swiglu

Q_BLOCK = 128
LN_EPS = 1e-5


def layer_norm(x, p):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + LN_EPS) * p["scale"].astype(F32) \
        + p["bias"].astype(F32)


def _rotated_head(x, positions, theta, n_rot: int):
    """``x`` (s, heads, d): the first ``n_rot`` channels rotated."""
    return jnp.concatenate(
        [rope(x[None, ..., :n_rot], positions, theta)[0], x[..., n_rot:]],
        axis=-1)


def index_parts(cfg, p, y, cq, positions, mm):
    """``(qI (s, n_I, d_I), kI (s, d_I), w (s, n_I))`` of one sequence."""
    n_i, d_i = cfg["index_n_heads"], cfg["index_head_dim"]
    theta, n_rot = cfg["rope_parameters"]["rope_theta"], \
        cfg["qk_rope_head_dim"]
    s = y.shape[0]
    q_i = _rotated_head(mm(cq, p["wq"]).reshape(s, n_i, d_i), positions,
                        theta, n_rot)
    k_i = _rotated_head(layer_norm(mm(y, p["wk"]), p["k_norm"])[:, None, :],
                        positions, theta, n_rot)[:, 0]
    w = mm(y, p["ww"]) * (n_i ** -0.5 * d_i ** -0.5)
    return q_i, k_i, w


def _blocks(s: int):
    n = -(-s // Q_BLOCK)
    return n, n * Q_BLOCK - s


def select(cfg, q_i, k_i, w, mm):
    """``S_t`` of every query of one sequence as positions: ``(s, k)``
    int32, ``-1`` where a query has fewer than ``k`` positions behind
    it."""
    s, k = q_i.shape[0], min(cfg["index_topk"], q_i.shape[0])
    n, extra = _blocks(s)
    pad = lambda x: jnp.pad(x, ((0, extra),) + ((0, 0),) * (x.ndim - 1))
    at = jnp.arange(s)

    def block(args):
        q_b, w_b, t = args                       # (B, n_I, d_I), (B, n_I), (B,)
        sc = jax.nn.relu(mm(q_b, k_i.T))         # (B, n_I, s)
        score = jnp.sum(sc * w_b[..., None], axis=1)
        seen = at[None, :] <= t[:, None]
        _, chosen = jax.lax.top_k(jnp.where(seen, score, -jnp.inf), k)
        return jnp.where(chosen <= t[:, None], chosen, -1)

    cut = lambda x: pad(x).reshape((n, Q_BLOCK) + x.shape[1:])
    out = jax.lax.map(block, (cut(q_i), cut(w), cut(at)))
    return out.reshape(n * Q_BLOCK, k)[:s].astype(jnp.int32)


def attention(cfg, q, k_n, k_r, v, chosen, mm):
    """Softmax attention of every query over the positions ``chosen``
    names for it, its scores written out a block of queries at a time.
    ``q`` (s, h, nope + rope) with its rope part rotated, ``k_n`` (s, h,
    nope), ``k_r`` (s, rope) rotated and shared by the heads, ``v`` (s,
    h, dv), ``chosen`` (s, k) positions, ``-1`` none."""
    dn = cfg["qk_nope_head_dim"]
    s, h, dqk = q.shape
    n, extra = _blocks(s)
    pad = lambda x: jnp.pad(x, ((0, extra),) + ((0, 0),) * (x.ndim - 1),
                            constant_values=-1 if x.dtype == jnp.int32 else 0)
    kt_n = jnp.transpose(k_n, (1, 2, 0))                      # (h, nope, s)
    vh = jnp.swapaxes(v, 0, 1)                                # (h, s, dv)

    def block(args):
        q_b, c_b = args                           # (B, h, dqk), (B, k)
        named = jnp.zeros((Q_BLOCK, s), bool).at[
            jnp.arange(Q_BLOCK)[:, None],
            jnp.where(c_b >= 0, c_b, s)].set(True, mode="drop")
        qh = jnp.swapaxes(q_b, 0, 1)                          # (h, B, dqk)
        sc = (mm(qh[..., :dn], kt_n) + mm(qh[..., dn:], k_r.T)) \
            / jnp.sqrt(F32(dqk))
        sc = jnp.where(named[None], sc, -jnp.inf)
        # A padded query names nothing: its row is all -inf, and zeros.
        p = jnp.where(named[None], jax.nn.softmax(
            jnp.where(named.any(-1)[None, :, None], sc, 0.0), axis=-1), 0.0)
        return jnp.swapaxes(mm(p, vh), 0, 1)                  # (B, h, dv)

    cut = lambda x: pad(x).reshape((n, Q_BLOCK) + x.shape[1:])
    o = jax.lax.map(block, (cut(q), cut(chosen)))
    return o.reshape(n * Q_BLOCK, -1)[:s]


def mixer(cfg, p, y, positions, chosen, scores: bool, mm):
    """The attention branch of one sequence ``y`` (s, d) and the
    selection it attended: its own where the layer ``scores``, else
    ``chosen``, the one handed up from below."""
    eps, theta = cfg["rms_norm_eps"], cfg["rope_parameters"]["rope_theta"]
    h = cfg["num_attention_heads"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    rank = cfg["kv_lora_rank"]
    s = y.shape[0]
    cq = rms_norm(mm(y, p["wqa"]), p["q_norm"]["scale"], eps)
    q = mm(cq, p["wq"]).reshape(s, h, dn + dr)
    q = jnp.concatenate(
        [q[..., :dn], rope(q[None, ..., dn:], positions, theta)[0]], axis=-1)
    latent = mm(y, p["wa"])
    ckv = rms_norm(latent[..., :rank], p["kv_norm"]["scale"], eps)
    k_r = rope(latent[None, :, None, rank:], positions, theta)[0, :, 0]
    kv = mm(ckv, p["wb"]).reshape(s, h, dn + dv)
    if scores:
        chosen = select(cfg, *index_parts(cfg, p["index"], y, cq, positions,
                                          mm), mm)
    o = attention(cfg, q, kv[..., :dn], k_r, kv[..., dn:], chosen, mm)
    return mm(o, p["wo"]), chosen


def routing(cfg, p, m, mm):
    """Per token the weight of every expert of the router's full width
    (0 where not chosen): sigmoid scores, the top-k of score + selection
    bias, the chosen scores renormalised and scaled."""
    width = cfg["published"]["n_routed_experts"]
    s = jax.nn.sigmoid(mm(m, p["router"]))
    _, chosen = jax.lax.top_k(s + p["bias"].astype(F32),
                              cfg["num_experts_per_tok"])
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    w = cfg["routed_scaling_factor"] * picked \
        / jnp.sum(picked, axis=-1, keepdims=True)
    return jnp.sum(jax.nn.one_hot(chosen, width, dtype=F32) * w[..., None],
                   axis=-2)


def experts(cfg, p, m, mm, first=None, held=None, shared: bool = True):
    """The shared expert plus the held experts' weighted outputs, one
    expert at a time over every token."""
    first = cfg["deployment_share"]["first_expert"] if first is None \
        else first
    held = cfg["n_routed_experts"] if held is None else held
    weight = routing(cfg, p, m, mm)[..., first:first + held]

    def add(y, expert):
        w1, w2, w_e = expert
        return y + w_e[..., None] * swiglu(m, w1, w2, mm), None

    start = swiglu(m, p["shared_w1"], p["shared_w2"], mm) if shared \
        else jnp.zeros_like(m)
    y, _ = jax.lax.scan(add, start, (p["w1"][:held], p["w2"][:held],
                                     jnp.moveaxis(weight, -1, 0)))
    return y


def layer(cfg, blk, x, positions, chosen, scores: bool, mm):
    """One layer on one sequence ``x`` (s, d): ``(out, S)``."""
    eps = cfg["rms_norm_eps"]
    o, chosen = mixer(cfg, blk["mixer"], rms_norm(x, blk["ln1"]["scale"],
                                                  eps),
                      positions, chosen, scores, mm)
    a = x + o
    m = rms_norm(a, blk["ln2"]["scale"], eps)
    if "experts" in blk:
        f = experts(cfg, blk["experts"], m, mm)
    else:
        f = swiglu(m, blk["w1"], blk["w2"], mm)
    return a + f, chosen


# -------------------------------------------------------------- serving

def _plain(cfg: dict) -> tuple:
    """The configuration's plain values and the nested groups the layer
    reads, as a hashable jit argument."""
    return _static(cfg) + tuple(
        (k, _static(cfg[k]))
        for k in ("deployment_share", "published", "rope_parameters"))


def _unplain(key: tuple) -> dict:
    return {k: dict(v) if isinstance(v, tuple) else v for k, v in key}


@partial(jax.jit, static_argnames=("cfg", "mm", "scores"))
def _layer_fwd(cfg, mm, scores, blk, x, chosen):
    return layer(_unplain(cfg), blk, x, jnp.arange(x.shape[0]), chosen,
                 scores, MATMULS[mm])


@partial(jax.jit, static_argnames=("cfg", "mm"))
def _head_at(cfg, mm, top, x, rows):
    xr = jnp.take_along_axis(x, rows[..., None], axis=1)
    return head_logits(_unplain(cfg), top, xr, MATMULS[mm])


def forward(cfg: dict, layers, x, mm: str = "f32", selections=None):
    """The stack over the embedded rows ``x`` (b, s, d), a layer at a
    time (``layers`` yields each layer's leaves in turn) and a sequence
    at a time.  ``selections``, a list, receives every layer's ``S``
    ``(b, s, k)``."""
    key = _plain(cfg)
    chosen = [None] * x.shape[0]
    with jax.default_matmul_precision("highest"):
        for index, blk in enumerate(layers):
            kind = cfg["indexer_types"][index]
            if kind not in ("full", "shared"):
                raise ValueError(f"indexer_types[{index}] = {kind!r}")
            if kind == "shared" and chosen[0] is None:
                raise ValueError(
                    f"layer {index} shares a selection and no layer below "
                    "it makes one")
            outs = [_layer_fwd(key, mm, kind == "full", blk, x[i], chosen[i])
                    for i in range(x.shape[0])]
            # One layer's leaves at a time: without the wait the host
            # runs ahead and the device holds every queued layer's.
            x = jax.block_until_ready(jnp.stack([o for o, _ in outs]))
            chosen = [c for _, c in outs]
            if selections is not None:
                selections.append(jnp.stack(chosen))
    return x


def logits_at(cfg: dict, top, layers, tokens, rows, mm: str = "f32"):
    """Full forward over ``tokens`` (b, s), one layer and one sequence
    at a time, and the logits at positions ``rows`` (b, n): (b, n,
    vocab)."""
    x = forward(cfg, layers, top["embed"].astype(F32)[tokens], mm)
    with jax.default_matmul_precision("highest"):
        return _head_at(_plain(cfg), mm, top, x, rows)
