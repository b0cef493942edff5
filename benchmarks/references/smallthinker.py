"""Plain float32 reference of the SmallThinker decoder
(``smallthinker_21b_instruct``): pre-norm residual blocks of grouped-query
attention and a top-k layer of ReGLU experts whose router reads the
layer's INPUT, before the first norm and the attention; the first layer
of every four attends every position before it and has no position
signal, the other three attend a window and rotate; no shared expert, no
dense layer, no bias, no gate and no norm on queries or keys; rmsnorm,
untied head, mean next-token cross-entropy.  Straightforward
``jax.numpy``: no kernel, no exchange, every expert of a layer held here.
It imports nothing of the program.

``x`` a layer's input (the residual stream), ``N`` an rmsnorm with its
own scale, ``h`` query heads of ``hd`` channels on ``h_kv`` key-value
heads::

    r   = x Wr                                 the router reads x itself
    top = the k largest of r;  w = softmax over those k of r
    a   = N1(x);  [q | k | v] = a Wqkv          q: h x hd;  k, v: h_kv x hd
    sliding layer:  q, k = rope(q, pos), rope(k, pos)   (theta, all hd channels)
                    position i reads j <= i with i - j < window
    full layer:     no rotation; position i reads every j <= i
    o_h = softmax(q_h . k_{h // (h / h_kv)} / sqrt(hd)) v_{h // (h / h_kv)}
    u   = x + o Wo
    m   = N2(u)
    y   = u + sum_{e in top} w_e W2_e (relu(Wg_e m) * Wu_e m)
    logits = N_f(y_last) W_head

Memory and time are bounded, never the arithmetic.  Attention writes its
scores out a block of queries at a time, against every key on a full
layer and against the window's span of keys on a sliding one.  The
experts are a loop over all of them, eight at a time: each expert runs as
a dense product over the table of the tokens that chose it, and the
table is as long as the fullest expert's, read off the routing before
the layer's program is made (a Python int: a seed whose fullest expert
passes the next 512 rows compiles that layer anew), so the whole costs
about 4/3 of the rows' own products and not 64/6 of them.  ``step`` walks the
chain rule back one layer at a time with ``jax.vjp``; the devices that
hold the batch each take their own rows of it (a sequence is a device's
own work from the embedding to the loss) in one program over those
devices (``jax.shard_map`` and the mean of their gradients, nothing of
the program under test), and each leaf is rounded at once in its own
sharding.

Weights come leaf by leaf from ``benchmarks/families/smallthinker.py`` in
the layout the configuration file states, are cast to float32 and
multiplied at ``highest`` precision.  ``mm="fp8"`` is the control of "How
correct is decided" (``references/dense_decoder.py`` has the recipe).
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.references.dense_decoder import (
    F32, MATMULS, _round, _static, head_loss, rms_norm, rope)

# Queries a block of the written-out scores holds; what an expert's table
# of rows is a multiple of; experts whose tables are held at once.
QUERY_BLOCK = 256
ROW_BLOCK = 512
EXPERT_GROUP = 8


class Plan(NamedTuple):
    """The configuration's plain numbers, hashable."""
    eps: float
    heads: int
    kv_heads: int
    head_dim: int
    theta: float
    experts: int
    top_k: int
    kinds: tuple          # per layer (window or 0, rotated)


def plan(cfg: dict) -> Plan:
    n = cfg["num_hidden_layers"]
    sliding, rotated = cfg["sliding_window_layout"], cfg["rope_layout"]
    if len(sliding) != n or len(rotated) != n:
        raise ValueError("sliding_window_layout and rope_layout name every "
                         f"layer 0..{n - 1} once")
    if not cfg["moe_primary_router_apply_softmax"] \
            or not cfg["norm_topk_prob"] or cfg["rope_scaling"] \
            or cfg["tie_word_embeddings"]:
        raise ValueError("smallthinker: softmax routing renormalised over "
                         "the chosen, no rope scaling and an untied head "
                         "are what is written")
    return Plan(cfg["rms_norm_eps"], cfg["num_attention_heads"],
                cfg["num_key_value_heads"], cfg["head_dim"],
                float(cfg["rope_theta"]), cfg["moe_num_primary_experts"],
                cfg["moe_num_active_primary_experts"],
                tuple((cfg["sliding_window_size"] if sliding[i] else 0,
                       bool(rotated[i])) for i in range(n)))


# -------------------------------------------------------------- attention

def attention(q, k, v, window: int, mm):
    """Causal softmax attention of one sequence with its scores written
    out, a block of queries at a time: ``q`` (s, h, hd), ``k`` and ``v``
    (s, h_kv, hd); ``window > 0`` keeps of a query's positions its own
    and the ``window - 1`` before it, and a block's scores are then taken
    against the ``window + block`` keys that end with the block's last."""
    s, h, hd = q.shape
    h_kv = k.shape[1]
    g = h // h_kv
    blk = min(QUERY_BLOCK, s)
    n = -(-s // blk)
    lead = min(window, s) if window else 0
    span = lead + blk if window else n * blk
    qp = jnp.pad(q, ((0, n * blk - s), (0, 0), (0, 0)))
    # Key j lies at row j + lead; rows before 0 and past s are masked.
    pad = ((lead, n * blk - s), (0, 0), (0, 0))
    kp, vp = jnp.pad(k, pad), jnp.pad(v, pad)

    @jax.checkpoint
    def block(b):
        i = b * blk + jnp.arange(blk)[:, None]
        at = (b * blk if window else 0)
        j = at - lead + jnp.arange(span)[None, :]
        keep = (j <= i) & (j >= 0) & (j < s)
        if window:
            keep = keep & (i - j < window)
        qb = jax.lax.dynamic_slice_in_dim(qp, b * blk, blk, 0)
        kb = jax.lax.dynamic_slice_in_dim(kp, at, span, 0)
        vb = jax.lax.dynamic_slice_in_dim(vp, at, span, 0)
        # (h_kv, g * blk, hd): row r of a group is query r % blk of the
        # group's head r // blk.
        qg = qb.reshape(blk, h_kv, g, hd).transpose(1, 2, 0, 3).reshape(
            h_kv, g * blk, hd)
        sc = mm(qg, jnp.transpose(kb, (1, 2, 0))) / jnp.sqrt(F32(hd))
        # Finite, not -inf: a query that fills the last block up may see
        # no key at all, and its row, which nothing reads, must not turn
        # the way back into NaN.
        sc = jnp.where(jnp.tile(keep, (g, 1))[None], sc, F32(-1e30))
        o = mm(jax.nn.softmax(sc, axis=-1), jnp.transpose(vb, (1, 0, 2)))
        return o.reshape(h_kv, g, blk, hd).transpose(2, 0, 1, 3).reshape(
            blk, h, hd)

    o = jax.lax.map(block, jnp.arange(n))
    return o.reshape(n * blk, h, hd)[:s]


def mixer(pl: Plan, p, a, kind: tuple, mm):
    """The attention branch of one sequence ``a`` (s, d), normed."""
    window, rotated = kind
    h, h_kv, hd = pl.heads, pl.kv_heads, pl.head_dim
    s = a.shape[0]
    proj = mm(a, p["wqkv"])
    q = proj[:, :h * hd].reshape(s, h, hd)
    k = proj[:, h * hd:(h + h_kv) * hd].reshape(s, h_kv, hd)
    v = proj[:, (h + h_kv) * hd:].reshape(s, h_kv, hd)
    if rotated:
        positions = jnp.arange(s)
        q = rope(q[None], positions, pl.theta)[0]
        k = rope(k[None], positions, pl.theta)[0]
    return mm(attention(q, k, v, window, mm).reshape(s, h * hd), p["wo"])


# ---------------------------------------------------------------- experts

def routing(pl: Plan, p, x, mm):
    """``(chosen (s, k), weights (s, k))`` of the rows ``x`` the router
    reads: the k largest router outputs, softmax over those k."""
    top, chosen = jax.lax.top_k(mm(x, p["router"]), pl.top_k)
    return chosen, jax.nn.softmax(top, axis=-1)


def reglu(x, w1, w2, mm):
    gate, up = jnp.split(mm(x, w1), 2, axis=-1)
    return mm(jax.nn.relu(gate) * up, w2)


def rows_of_fullest(pl: Plan, chosen) -> int:
    """The rows of the expert that a sequence's tokens chose most often
    (``chosen`` (..., s, k); the largest over the leading axes), rounded
    up to whole :data:`ROW_BLOCK` s: how long a table :func:`experts`
    needs an expert."""
    counts = jnp.sum(jax.nn.one_hot(chosen, pl.experts, dtype=jnp.int32),
                     axis=(-3, -2))
    return -(-int(jnp.max(counts)) // ROW_BLOCK) * ROW_BLOCK


def experts(pl: Plan, p, m, chosen, weight, mm, room: int):
    """``sum over a token's chosen e of w_e E_e(m)`` for one sequence
    ``m`` (s, d): for every expert the table of the tokens that chose it
    (``room`` entries, a Python int no smaller than the fullest expert's
    rows, :func:`rows_of_fullest`; the entries behind an expert's last
    read a row of zeros and add nothing), the expert as a dense product
    over its table, the rows added to their tokens under their weights;
    :data:`EXPERT_GROUP` experts at a time (each group's pass is
    rematerialised on the way back)."""
    s, d = m.shape
    k, n = pl.top_k, pl.experts
    flat = chosen.reshape(-1)
    order = jnp.argsort(flat, stable=True)
    counts = jnp.sum(jax.nn.one_hot(flat, n, dtype=jnp.int32), axis=0)
    slot = jnp.arange(room)[None, :]
    pair = order[jnp.minimum((jnp.cumsum(counts) - counts)[:, None] + slot,
                             s * k - 1)]
    held = slot < counts[:, None]
    token = jnp.where(held, pair // k, s)                       # (n, room)
    share = jnp.where(held, weight.reshape(-1)[pair], 0)
    rows = jnp.pad(m, ((0, 1), (0, 0)))                         # row s: zeros
    group = min(EXPERT_GROUP, n)
    grouped = lambda a: a.reshape(n // group, group, *a.shape[1:])

    @jax.checkpoint
    def add(y, these):
        w1, w2, token, share = these
        out = reglu(rows[token], w1, w2, mm) * share[..., None]
        return y.at[token.reshape(-1)].add(out.reshape(-1, d)), None

    y, _ = jax.lax.scan(add, jnp.zeros((s + 1, d), F32),
                        tuple(map(grouped, (p["w1"], p["w2"], token, share))))
    return y[:s]


# ------------------------------------------------------------------ layer

def sequence_layer(pl: Plan, kind: tuple, blk, x, mm, room: int):
    """One block on one sequence ``x`` (s, d)."""
    chosen, weight = routing(pl, blk["experts"], x, mm)
    u = x + mixer(pl, blk["mixer"], rms_norm(x, blk["ln1"]["scale"], pl.eps),
                  kind, mm)
    m = rms_norm(u, blk["ln2"]["scale"], pl.eps)
    return u + experts(pl, blk["experts"], m, chosen, weight, mm, room)


def layer(pl: Plan, kind: tuple, blk, x, mm, room: int):
    """One block on ``x`` (b, s, d), a sequence at a time."""
    return jnp.stack([sequence_layer(pl, kind, blk, row, mm, room)
                      for row in x])


@partial(jax.jit, static_argnames=("pl", "mm"))
def _chosen(pl, mm, router, x):
    with jax.default_matmul_precision("highest"):
        return routing(pl, {"router": router.astype(F32)}, x, MATMULS[mm])[0]


def layer_room(pl: Plan, blk, x, mm: str) -> int:
    """:func:`rows_of_fullest` for a layer's input ``x`` (b, s, d)."""
    return rows_of_fullest(pl, _chosen(pl, mm, blk["experts"]["router"], x))


def logits(cfg: dict, params, tokens, mm: str = "f32"):
    """``(b, s, vocab)`` float32, for the tests at small sizes."""
    pl = plan(cfg)
    with jax.default_matmul_precision("highest"):
        p = jax.tree.map(lambda a: a.astype(F32), params)
        x = p["embed"][tokens]
        for kind, blk in zip(pl.kinds, p["blocks"]):
            x = layer(pl, kind, blk, x, MATMULS[mm],
                      layer_room(pl, blk, x, mm))
        return MATMULS[mm](rms_norm(x, p["ln_f"]["scale"], pl.eps),
                           p["unembed"])


# --------------------------------------------------------------- training

ROWS = "rows"


def _mesh_of(tokens):
    """The devices that hold the batch as a mesh of one axis, in the
    devices' order; one device where the batch's rows do not deal evenly
    over them."""
    from jax.sharding import Mesh

    devices = sorted(tokens.devices(), key=lambda d: d.id)
    if tokens.shape[0] % len(devices):
        devices = devices[:1]
    return Mesh(np.asarray(devices), (ROWS,))


def _by_rows(mesh, body, n_alike: int, n_rows: int, out):
    """``body`` with each device of ``mesh`` on its own rows of the
    batch: the first ``n_alike`` arguments whole on every device
    (parameters; a leaf that arrives laid over the devices is gathered by
    the compiler), the next ``n_rows`` split along their first axis;
    ``out`` says which results are alike everywhere (``P()``) and which
    are rows."""
    from jax.sharding import PartitionSpec as P

    return jax.shard_map(
        body, mesh=mesh, in_specs=(P(),) * n_alike + (P(ROWS),) * n_rows,
        out_specs=tuple(P(ROWS) if o else P() for o in out), check_vma=False)


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(F32), tree)


@partial(jax.jit, static_argnames=("mesh",))
def _embed_fwd(mesh, embed, tokens):
    (x,) = _by_rows(mesh, lambda e, t: (e.astype(F32)[t],), 1, 1, (1,))(
        embed, tokens)
    return x


@partial(jax.jit, static_argnames=("mesh", "pl", "kind", "mm", "room"))
def _layer_fwd(mesh, pl, kind, mm, room, blk, x):
    (y,) = _by_rows(mesh, lambda b_, x_: (
        layer(pl, kind, _f32(b_), x_, MATMULS[mm], room),), 1, 1, (1,))(
            blk, x)
    return y


@partial(jax.jit, static_argnames=("mesh", "pl", "kind", "mm", "room"),
         donate_argnums=(6,))
def _layer_bwd(mesh, pl, kind, mm, room, blk, gx, x):
    """``(the layer's gradient, mean over the devices; gx, rows)``."""
    def body(blk, gx, x):
        _, vjp = jax.vjp(lambda b_, x_: layer(
            pl, kind, b_, x_, MATMULS[mm], room), _f32(blk), x)
        gblk, gx = vjp(gx)
        return jax.lax.pmean(gblk, ROWS), gx

    return _by_rows(mesh, body, 1, 2, (0, 1))(blk, gx, x)


@partial(jax.jit, static_argnames=("mesh", "cfg", "mm"))
def _head_bwd(mesh, cfg, mm, head, x, tokens):
    """``(loss, the head's gradient, gx)``: loss and gradient the mean
    over the devices' own rows, ``gx`` each device's of its own mean."""
    def body(head, x, tokens):
        loss, (ghead, gx) = jax.value_and_grad(
            lambda h_, x_: head_loss(dict(cfg), h_, x_, tokens, MATMULS[mm]),
            argnums=(0, 1))(_f32(head), x)
        return jax.lax.pmean(loss, ROWS), jax.lax.pmean(ghead, ROWS), gx

    return _by_rows(mesh, body, 1, 2, (0, 0, 1))(head, x, tokens)


@partial(jax.jit, static_argnames=("mesh",))
def _embed_bwd(mesh, embed, tokens, gx):
    (g,) = _by_rows(mesh, lambda e, t, g: (jax.lax.pmean(
        jnp.zeros(e.shape, F32).at[t].add(g), ROWS),), 1, 2, (0,))(
            embed, tokens, gx)
    return g


@partial(jax.jit, static_argnames=("lr",))
def _sgd_leaf(lr, leaf, g):
    """SGD on one leaf, rounded once, and the gradient's norm."""
    return _round(leaf, g, lr, leaf.dtype), jnp.linalg.norm(g.ravel())


def _update(tree, grads, lr: float):
    """``(new leaves, gradient norms)`` of one part of the parameters,
    each new leaf laid out as the old one was."""
    both = jax.tree.map(partial(_sgd_leaf, lr), tree, grads)
    pick = lambda i: jax.tree.map(lambda _, b: b[i], tree, both)
    return jax.tree.map(lambda old, new: jax.device_put(new, old.sharding),
                        tree, pick(0)), pick(1)


def step(cfg: dict, params, tokens, lr: float, mm: str = "f32"):
    """One SGD step of the reference on a parameter tree in the
    configuration's dtype; returns ``(loss, new_params, grad_norms)``,
    the last a tree like the parameters with each leaf's float32
    gradient norm (of the mean loss over the whole batch).  Layer by
    layer: forward keeps the layer boundaries, backward re-runs one layer
    under ``jax.vjp`` and rounds its new leaves at once.  Each device
    that holds ``tokens`` follows its own rows of the batch from the
    embedding to the loss with the whole layer before it (one program
    over those devices, :func:`_by_rows`: the compiler gathers a leaf
    that is laid over them and the gradients' mean is one
    ``jax.lax.pmean``; carried from device to device with
    ``jax.device_put`` instead, a layer's 1.5 GB of float32 gradients and
    0.75 GB of weights a device made a step of the cell two minutes, of
    which the arithmetic was ten seconds: PERF.md section 6, PR 48)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    pl, key = plan(cfg), _static(cfg)
    mesh = _mesh_of(tokens)
    tokens = jax.device_put(tokens, NamedSharding(mesh, P(ROWS)))
    blocks = params["blocks"]
    head = {"ln_f": params["ln_f"], "unembed": params["unembed"]}
    with jax.default_matmul_precision("highest"):
        xs, rooms = [_embed_fwd(mesh, params["embed"], tokens)], []
        for kind, blk in zip(pl.kinds, blocks):
            rooms.append(layer_room(pl, blk, xs[-1], mm))
            xs.append(_layer_fwd(mesh, pl, kind, mm, rooms[-1], blk, xs[-1]))
        loss, ghead, gx = _head_bwd(mesh, key, mm, head, xs.pop(), tokens)
        new, norms = _update(head, ghead, lr)
        new["blocks"], norms["blocks"] = ([None] * len(blocks) for _ in "ab")
        for i in reversed(range(len(blocks))):
            gblk, gx = _layer_bwd(mesh, pl, pl.kinds[i], mm, rooms[i],
                                  blocks[i], gx, xs.pop())
            new["blocks"][i], norms["blocks"][i] = _update(
                blocks[i], gblk, lr)
        new["embed"], norms["embed"] = _update(
            params["embed"], _embed_bwd(mesh, params["embed"], tokens, gx),
            lr)
    return float(loss), new, norms


def sgd_step(cfg: dict, params, tokens, lr: float, mm: str = "f32"):
    """``(loss, new_params)``: the same entry as
    ``dense_decoder.sgd_step``."""
    return step(cfg, params, tokens, lr, mm)[:2]
