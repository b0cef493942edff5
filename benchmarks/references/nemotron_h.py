"""Plain float32 reference of the Nemotron-H layer stack (``nemotron_h``:
Nemotron-3-Super) as one chip of a four-chip group sees it: every layer
is ONE part, a Mamba-2 state-space mixer (``M``), the configuration's
grouped-query attention with no position signal (``*``), or an expert
layer whose routed experts work in a latent behind one shared down- and
up-projection (``E``).  Straightforward ``jax.numpy``: no kernel, no
cache, no chunked scan, no batching trick.  It imports nothing of the
program.

``x`` the stream, ``N`` an rmsnorm with its own scale (eps 1e-5), no
bias but the convolution's, ``t`` a position::

    x <- x + Part_l(N(x))     Part_l by the l-th letter of hybrid_override_pattern
    logits = N_f(x) W_head

    M:  [z | xBC | dt] = u W_in
        xBC'_t  = silu(b_c + sum_{j=0..3} w_c[j] * xBC_{t-3+j})    zeros before the start
        [x | B | C] = xBC'_t        x: heads x 64;  B, C: groups x 128
        delta_t = softplus(dt_t + dt_bias);   a_t = exp(-exp(A_log) delta_t)   a head
        H_t     = a_t H_{t-1} + delta_t x_t (x) B_t           H_{-1} = 0, one token at a time
        y_t     = H_t C_t + D x_t
        out     = [N_grouped(y * silu(z))] W_out     rmsnorm over each group's channels

    *:  q, k, v = u Wqkv (32 query heads, 2 KV heads, head h reads KV head h // 16)
        causal softmax(q . k / sqrt(128)) v, no rotation, no position table;  out Wo

    E:  p = sigmoid(m Wr);  C = the top-k of p + b;  w_e = scale p_e / sum_C p
        z = m W_down;  r = sum_{e in C, e held} w_e W2_e relu(W1_e z)^2
        out = r W_up + Ws2 relu(Ws1 m)^2

What the experts held elsewhere would add is left out, as the program
leaves it out; with ``W_up`` linear the four shares' ``r W_up`` add up to
the whole layer's, the shared expert counted once
(``tests/test_nemotron_h.py``).  Departures from the published model,
each shared with the program and listed in the configuration file under
``assumed``: attention without any position signal; the gated norm gates
first and normalises a group; ``delta`` is not clamped; selection bias
zeros; no multi-token-prediction module; the fused ``in_proj`` column
order ``[z | x | B | C | dt]``.

One sequence and one layer at a time.  Weights come leaf by leaf from
``benchmarks/families/nemotron_h.py``, are cast to float32 and
multiplied at ``highest`` precision.  ``mm="fp8"`` is the control of
"How correct is decided" (``references/dense_decoder.py`` has the
recipe): every matrix product's operands rounded to float8; the
recurrence's elementwise arithmetic is no product and stays float32.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from benchmarks.references.dense_decoder import (F32, MATMULS, _static,
                                                 attention, rms_norm)
from benchmarks.references.openpangu_moe import head_logits


def kind(cfg: dict, index: int) -> str:
    letter = cfg["hybrid_override_pattern"][index]
    if letter not in "ME*":
        raise ValueError(f"hybrid_override_pattern[{index}] = {letter!r}")
    return letter


def relu2(x, w1, w2, mm):
    return mm(jnp.square(jax.nn.relu(mm(x, w1))), w2)


def mamba2(cfg, p, u, mm):
    """The Mamba-2 branch of one sequence ``u`` (s, d), normed."""
    heads, hd = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    groups, n, taps = cfg["n_groups"], cfg["ssm_state_size"], \
        cfg["conv_kernel"]
    di, s = heads * hd, u.shape[0]
    proj = mm(u, p["in_proj"])
    z, xbc, dt = proj[:, :di], proj[:, di:di + di + 2 * groups * n], \
        proj[:, di + di + 2 * groups * n:]
    padded = jnp.pad(xbc, ((taps - 1, 0), (0, 0)))
    conv = p["conv_bias"].astype(F32) + sum(
        p["conv"][j].astype(F32) * padded[j:j + s] for j in range(taps))
    conv = jax.nn.silu(conv)
    x = conv[:, :di].reshape(s, heads, hd)
    B = conv[:, di:di + groups * n].reshape(s, groups, n)
    C = conv[:, di + groups * n:].reshape(s, groups, n)
    delta = jax.nn.softplus(dt + p["dt_bias"].astype(F32))       # (s, heads)
    a = jnp.exp(-jnp.exp(p["a_log"].astype(F32)) * delta)
    of_head = lambda t: jnp.repeat(t, heads // groups, axis=0)   # (heads, n)

    def token(H, inp):
        x_t, B_t, C_t, d_t, a_t = inp
        H = a_t[:, None, None] * H \
            + (d_t[:, None] * x_t)[:, :, None] * of_head(B_t)[:, None, :]
        return H, jnp.sum(H * of_head(C_t)[:, None, :], axis=-1)

    _, y = jax.lax.scan(token, jnp.zeros((heads, hd, n), F32),
                        (x, B, C, delta, a))
    y = y + p["d"].astype(F32)[:, None] * x
    gated = y.reshape(s, di) * jax.nn.silu(z)
    grouped = gated.reshape(s, groups, di // groups)
    normed = grouped / jnp.sqrt(
        jnp.mean(jnp.square(grouped), axis=-1, keepdims=True)
        + cfg["layer_norm_epsilon"])
    return mm(normed.reshape(s, di) * p["norm"]["scale"].astype(F32),
              p["out_proj"])


def own_attention(cfg, blk, u, mm):
    """Grouped-query causal attention of one sequence ``u`` (s, d) with
    no position signal."""
    h, h_kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        cfg["head_dim"]
    s = u.shape[0]
    qkv = mm(u, blk["wqkv"])
    q = qkv[:, :h * hd].reshape(1, s, h, hd)
    k = qkv[:, h * hd:(h + h_kv) * hd].reshape(1, s, h_kv, hd)
    v = qkv[:, (h + h_kv) * hd:].reshape(1, s, h_kv, hd)
    return mm(attention(q, k, v, 0, mm)[0], blk["wo"])


def routing(cfg, p, m, mm):
    """Per token the weight of every expert of the router's full width
    (0 where not chosen)."""
    width = cfg["published"]["n_routed_experts"]
    score = jax.nn.sigmoid(mm(m, p["router"]))
    _, chosen = jax.lax.top_k(score + p["bias"].astype(F32),
                              cfg["num_experts_per_tok"])
    picked = jnp.take_along_axis(score, chosen, axis=-1)
    w = cfg["routed_scaling_factor"] * picked \
        / jnp.sum(picked, axis=-1, keepdims=True)
    return jnp.sum(jax.nn.one_hot(chosen, width, dtype=F32) * w[..., None],
                   axis=-2)


def experts(cfg, p, m, mm, first=None, held=None, shared: bool = True):
    """The shared expert at the stream's width plus the held experts'
    weighted outputs in the latent, brought up once; one expert at a
    time over every token."""
    first = cfg["deployment_share"]["first_expert"] if first is None \
        else first
    held = cfg["n_routed_experts"] if held is None else held
    weight = routing(cfg, p, m, mm)[..., first:first + held]
    z = mm(m, p["down"])

    def add(r, expert):
        w1, w2, w_e = expert
        return r + w_e[..., None] * relu2(z, w1, w2, mm), None

    r, _ = jax.lax.scan(add, jnp.zeros_like(z),
                        (p["w1"][:held], p["w2"][:held],
                         jnp.moveaxis(weight, -1, 0)))
    out = mm(r, p["up"])
    if shared:
        out = out + relu2(m, p["shared_w1"], p["shared_w2"], mm)
    return out


def layer(cfg, letter: str, blk, x, mm):
    """One layer on one sequence ``x`` (s, d)."""
    eps = cfg["layer_norm_epsilon"]
    if letter == "E":
        return x + experts(cfg, blk["experts"],
                           rms_norm(x, blk["ln2"]["scale"], eps), mm)
    u = rms_norm(x, blk["ln1"]["scale"], eps)
    if letter == "M":
        return x + mamba2(cfg, blk["mixer"], u, mm)
    return x + own_attention(cfg, blk, u, mm)


# -------------------------------------------------------------- serving

def _plain(cfg: dict) -> tuple:
    """The configuration's plain values and the nested groups the layer
    reads, as a hashable jit argument."""
    return _static(cfg) + tuple(
        (k, _static(cfg[k])) for k in ("deployment_share", "published"))


def _unplain(key: tuple) -> dict:
    return {k: dict(v) if isinstance(v, tuple) else v for k, v in key}


@partial(jax.jit, static_argnames=("cfg", "mm", "letter"))
def _layer_fwd(cfg, mm, letter, blk, x):
    return layer(_unplain(cfg), letter, blk, x, MATMULS[mm])


@partial(jax.jit, static_argnames=("cfg", "mm"))
def _head_at(cfg, mm, top, x, rows):
    xr = jnp.take_along_axis(x, rows[..., None], axis=1)
    cfg = dict(_unplain(cfg))
    cfg["rms_norm_eps"] = cfg["layer_norm_epsilon"]
    return head_logits(cfg, top, xr, MATMULS[mm])


def forward(cfg: dict, layers, x, mm: str = "f32"):
    """The stack over the embedded rows ``x`` (b, s, d), a layer at a
    time (``layers`` yields each layer's leaves in turn) and a sequence
    at a time."""
    key = _plain(cfg)
    with jax.default_matmul_precision("highest"):
        for index, blk in enumerate(layers):
            # One layer's leaves at a time: without the wait the host
            # runs ahead and the device holds every queued layer's.
            x = jax.block_until_ready(jnp.stack(
                [_layer_fwd(key, mm, kind(cfg, index), blk, x[i])
                 for i in range(x.shape[0])]))
    return x


def logits_at(cfg: dict, top, layers, tokens, rows, mm: str = "f32"):
    """Full forward over ``tokens`` (b, s), one layer and one sequence
    at a time, and the logits at positions ``rows`` (b, n): (b, n,
    vocab)."""
    x = forward(cfg, layers, top["embed"].astype(F32)[tokens], mm)
    with jax.default_matmul_precision("highest"):
        return _head_at(_plain(cfg), mm, top, x, rows)
