"""Gated delta rule with a per-channel decay (Kimi Delta Attention).

One head keeps a state ``S`` of shape ``(d_k, d_v)``, zero at the start
of a sequence, and reads it out after each token::

    S_t = (I - beta_t k_t k_t^T) diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t * scale

``g_t <= 0`` is the log-decay of each of the ``d_k`` channels and
``beta_t`` in [0, 1] the write strength.  Two ways behind one signature:

* :func:`kda_recurrent` is the definition, one token at a time under
  ``lax.scan`` — the oracle, as ``flash.py`` keeps its jnp path;
* :func:`kda_chunked` is what the model runs: chunks of 64 tokens, inside
  a chunk the WY / UT-transform form as matrix products, across chunks a
  ``lax.scan`` over the state.  Plain JAX, differentiated by autodiff
  through the chunked form (no ``custom_vjp``): the backward is the
  transposed chunk products and a reverse scan, at the price of the
  chunk-level residuals autodiff keeps.  Those are some thirty float32
  arrays of the size of ``g`` (9 GB for 32 heads of 128 over 16,384
  tokens), so heads are taken ``HEAD_GROUP`` at a time, each group
  rematerialised on the way back: one more forward of the chunk
  products for a quarter of the memory.

With ``G_r`` the decay summed from the chunk's first token to token
``r``, ``S_0`` the state the chunk starts from and
``u_i = beta_i (v_i - S_{i-1}^T (exp(g_i) k_i))`` the value each token
really writes::

    S_r = diag(exp(G_r)) S_0 + sum_{i<=r} diag(exp(G_r - G_i)) k_i u_i^T
    (I + A) u = beta v - (beta k exp(G)) S_0,
        A_ri = beta_r sum_c k_rc k_ic exp(G_rc - G_ic)   (i < r)
    o_r = scale [(q_r exp(G_r)) S_0 + sum_{i<=r} B_ri u_i],
        B_ri = sum_c q_rc k_ic exp(G_rc - G_ic)          (i <= r)

The state, the decays and every sum are at least float32.  Matrix
products take their operands in the inputs' type (bfloat16 inputs: one
MXU pass, float32 accumulation), the state included, as an operand only.

``exp(G_r - G_i)`` does not factor over a whole chunk without overflow
(``exp(-G_i)`` after 63 fast-decaying tokens), so ``A`` and ``B`` are
built from blocks of 16 rows, each against all earlier columns, both
sides measured from the decay at the block's middle row: every exponent
then spans at most 8 tokens on its growing side.  Exponents are capped
at 80, which binds only where a channel decays by more than ``e^-10`` a
token for 8 tokens on end.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .flash import dot_precision

CHUNK = 64
HEAD_GROUP = 8
_SUB = 16
_EXP_CAP = 80.0


def _state_dtype(q):
    # At least f32; f64 inputs keep f64 (the x64 suite's oracles).
    return jnp.promote_types(q.dtype, jnp.float32)


def kda_recurrent(q, k, v, g, beta, scale=None):
    """The recurrence, token by token.  ``q``, ``k``, ``g``
    ``(b, s, h, d_k)``; ``v`` ``(b, s, h, d_v)``; ``beta`` ``(b, s, h)``.
    Returns ``o`` ``(b, s, h, d_v)`` in ``v``'s type."""
    ct = _state_dtype(q)
    prec = jax.lax.Precision.HIGHEST
    b, s, h, dk = q.shape
    scale = dk ** -0.5 if scale is None else scale
    seq = lambda x: jnp.moveaxis(x.astype(ct), 1, 0)

    def step(S, inp):
        q_t, k_t, v_t, g_t, b_t = inp
        S = S * jnp.exp(g_t)[..., None]
        seen = jnp.einsum("bhk,bhkv->bhv", k_t, S, precision=prec)
        S = S + jnp.einsum("bhk,bhv->bhkv", k_t * b_t[..., None],
                           v_t - seen, precision=prec)
        return S, jnp.einsum("bhk,bhkv->bhv", q_t, S, precision=prec) * scale

    S0 = jnp.zeros((b, h, dk, v.shape[-1]), ct)
    _, o = jax.lax.scan(step, S0, (seq(q), seq(k), seq(v), seq(g),
                                   seq(beta)))
    return jnp.moveaxis(o, 0, 1).astype(v.dtype)


def _decay_grams(q, k, G, mm, ct, prec):
    """``sum_c x_rc k_ic exp(G_rc - G_ic)`` for ``i <= r`` (zero above the
    diagonal), for ``x = k`` and ``x = q``: ``(..., C, C)`` each."""
    C = k.shape[-2]
    kk, qk = [], []
    for r0 in range(0, C, _SUB):
        r1 = min(r0 + _SUB, C)
        mid = (r0 + r1) // 2
        ref = G[..., mid:mid + 1, :]
        up = jnp.exp(jnp.minimum(G[..., r0:r1, :] - ref, _EXP_CAP))
        down = jnp.exp(jnp.minimum(ref - G[..., :r1, :], _EXP_CAP))
        rows = jnp.concatenate([k[..., r0:r1, :] * up,
                                q[..., r0:r1, :] * up], axis=-2)
        blk = jnp.einsum("...rc,...ic->...ri", rows.astype(mm),
                         (k[..., :r1, :] * down).astype(mm),
                         preferred_element_type=ct, precision=prec)
        blk = jnp.pad(blk, [(0, 0)] * (blk.ndim - 1) + [(0, C - r1)])
        kk.append(blk[..., :r1 - r0, :])
        qk.append(blk[..., r1 - r0:, :])
    lower = jnp.tril(jnp.ones((C, C), bool))
    return (jnp.where(lower, jnp.concatenate(kk, axis=-2), 0),
            jnp.where(lower, jnp.concatenate(qk, axis=-2), 0))


def kda_chunked(q, k, v, g, beta, scale=None, chunk: int = CHUNK):
    """The same function as :func:`kda_recurrent` in chunked form.  Any
    sequence length: the tail is padded with tokens that write nothing
    (``beta = 0``) and do not decay (``g = 0``)."""
    h = q.shape[2]
    scale = q.shape[-1] ** -0.5 if scale is None else scale
    core = functools.partial(_chunked_heads, scale=scale, chunk=chunk)
    if h <= HEAD_GROUP or h % HEAD_GROUP:
        return core(q, k, v, g, beta)
    # (b, s, h, ...) -> (groups, b, s, HEAD_GROUP, ...) and back.
    split = lambda x: jnp.moveaxis(x.reshape(
        *x.shape[:2], h // HEAD_GROUP, HEAD_GROUP, *x.shape[3:]), 2, 0)
    o = jax.lax.map(lambda a: jax.checkpoint(core)(*a),
                    tuple(map(split, (q, k, v, g, beta))))
    return jnp.moveaxis(o, 0, 2).reshape(*v.shape)


def _chunked_heads(q, k, v, g, beta, scale, chunk):
    ct = _state_dtype(q)
    mm = q.dtype
    prec = dot_precision(mm)
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    n = -(-s // chunk)

    def chunks(x):   # (b, s, h, ...) -> (b, h, n, chunk, ...), in ct
        x = jnp.pad(x.astype(ct), [(0, 0), (0, n * chunk - s)]
                    + [(0, 0)] * (x.ndim - 2))
        x = jnp.moveaxis(x, 2, 1)
        return x.reshape(b, h, n, chunk, *x.shape[3:])

    q, k, v, g, beta = map(chunks, (q, k, v, g, beta))
    G = jnp.cumsum(g, axis=-2)
    beta = beta[..., None]
    gram_kk, gram_qk = _decay_grams(q, k, G, mm, ct, prec)
    strict = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
    unit = jnp.eye(chunk, dtype=ct) + jnp.where(strict, beta * gram_kk, 0)
    # (I + A)^-1 applied to [beta k exp(G) | beta v]: the UT transform.
    rhs = jnp.concatenate([beta * k * jnp.exp(G), beta * v], axis=-1)
    wu = jax.scipy.linalg.solve_triangular(unit, rhs, lower=True,
                                           unit_diagonal=True)
    w, u = wu[..., :dk], wu[..., dk:]
    g_end = G[..., -1:, :]
    q_in = q * jnp.exp(G) * scale                 # reads S_0
    k_out = k * jnp.exp(g_end - G)                # writes the chunk's end
    gram_qk = gram_qk * scale

    def dot(eq, x, y):
        return jnp.einsum(eq, x.astype(mm), y.astype(mm),
                          preferred_element_type=ct, precision=prec)

    def step(S, inp):
        w_c, u_c, b_c, q_c, k_c, decay = inp
        u_c = u_c - dot("bhrk,bhkv->bhrv", w_c, S)
        o = dot("bhrk,bhkv->bhrv", q_c, S) + dot("bhri,bhiv->bhrv", b_c, u_c)
        S = S * decay[..., None] + dot("bhrk,bhrv->bhkv", k_c, u_c)
        return S, o

    lead = lambda x: jnp.moveaxis(x, 2, 0)
    _, o = jax.lax.scan(step, jnp.zeros((b, h, dk, dv), ct),
                        (lead(w), lead(u), lead(gram_qk), lead(q_in),
                         lead(k_out), lead(jnp.exp(g_end[..., 0, :]))))
    o = jnp.moveaxis(o, 0, 2).reshape(b, h, n * chunk, dv)[:, :, :s]
    return jnp.moveaxis(o, 1, 2).astype(v.dtype)
