"""Expert parallelism: capacity-based MoE dispatch over ``Alltoall``.

The reference has no MoE, but its ``Alltoall`` with per-rank-varying
``numelem`` is exactly the token-dispatch primitive (SURVEY.md §2.5 EP row;
reference: csrc/extension.cpp:947-979).  XLA wants static shapes, so the
ragged dispatch becomes the standard padded+masked *capacity* formulation
(SURVEY.md §7 hard part 2): every expert receives a fixed ``capacity`` slot
buffer per source rank, tokens beyond capacity are dropped (zero
contribution — route them through the residual connection), and the ragged
structure lives in the dispatch/combine masks, not the shapes.

Layout (experts rank-major: expert ``e`` lives on rank ``e // epr``):

    tokens (T, d) --top-1 router--> dispatch one-hot (T, E, C)
    send   (size, epr*C, d)   --Alltoall(ga=1, sa=0)-->  recv from all ranks
    expert FFN on (epr, size*C, d)   (batched einsum — MXU-shaped)
    return Alltoall (the same exchange; its adjoint is itself) --combine-->

Both transports are the one differentiable ``Alltoall`` op, so the entire
MoE layer is AD-transparent on either backend; gradients to expert weights
ride the reverse all-to-all automatically.

Beside it, the held-share layer (:func:`held_experts_ffn`): sigmoid or
softmax scores, top-k of ALL the experts with a selection bias, weights
renormalised or not, swiglu or relu² experts at the stream's width or in
a latent behind one shared down- and up-projection, a shared expert and
zero-compute (identity) experts behind the routed ones, for a rank that is told
which experts it holds and computes their part of the result for every
token routed to them — no capacity, nothing dropped.  Given an expert-
parallel communicator it is the whole layer
(:func:`exchanged_experts_ffn`): every (token, choice) row goes to the
rank that holds its expert and comes back, in rounds of a fixed buffer,
over :func:`~mpi4torch_tpu.ops.ragged.ragged_alltoall`, forward and
adjoint.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Dict

import jax
import jax.numpy as jnp

from ..runtime import CommError
from ..utils.profiling import layer_scope


def top1_route(router_logits, capacity: int):
    """Switch-style top-1 routing with a per-expert capacity.

    Returns ``(dispatch, combine, aux)``: a ``(T, E, C)`` boolean dispatch
    mask (token t occupies slot c of expert e), the same mask scaled by the
    router probability (the combine weights), and the load-balancing
    auxiliary loss ``E * sum_e f_e * P_e`` (Switch Transformer's; equals 1
    at perfect balance)."""
    T, E = router_logits.shape
    probs = jax.nn.softmax(router_logits, axis=-1)
    expert = jnp.argmax(probs, axis=-1)                       # (T,)
    gate = jnp.max(probs, axis=-1)                            # (T,)
    onehot = jax.nn.one_hot(expert, E, dtype=probs.dtype)     # (T, E)

    # Slot index of each token within its expert's buffer, in token order.
    pos = jnp.cumsum(onehot, axis=0) * onehot                 # 1-based
    pos = jnp.sum(pos, axis=-1) - 1.0                         # (T,)
    keep = pos < capacity

    slot = jax.nn.one_hot(jnp.clip(pos, 0, capacity - 1).astype(jnp.int32),
                          capacity, dtype=probs.dtype)        # (T, C)
    dispatch = (onehot[:, :, None] * slot[:, None, :]
                * keep[:, None, None].astype(probs.dtype))    # (T, E, C)
    combine = dispatch * gate[:, None, None]

    frac = jnp.mean(onehot, axis=0)                           # f_e
    mean_prob = jnp.mean(probs, axis=0)                       # P_e
    aux = E * jnp.sum(frac * mean_prob)
    return dispatch, combine, aux


def init_moe(key, n_experts: int, d_model: int, d_ff: int,
             dtype=jnp.float32) -> Dict[str, Any]:
    """Replicated parameter pytree for a MoE FFN with stacked expert weights
    (experts on axis 0, rank-major); each rank slices its shard with
    :func:`~mpi4torch_tpu.parallel.tp.shard_axis` inside :func:`moe_ffn`."""
    kg, k1, k2 = jax.random.split(key, 3)
    scale_in = 1.0 / jnp.sqrt(jnp.asarray(d_model, dtype))
    scale_out = 1.0 / jnp.sqrt(jnp.asarray(d_ff, dtype))
    return {
        "gate": jax.random.normal(kg, (d_model, n_experts), dtype) * scale_in,
        "w1": jax.random.normal(k1, (n_experts, d_model, d_ff), dtype) * scale_in,
        "b1": jnp.zeros((n_experts, d_ff), dtype),
        "w2": jax.random.normal(k2, (n_experts, d_ff, d_model), dtype) * scale_out,
        "b2": jnp.zeros((n_experts, d_model), dtype),
    }


def moe_ffn(comm, x, params: Dict[str, Any], capacity: int,
            activation=jax.nn.gelu):
    """Expert-parallel MoE FFN layer.

    ``x`` is this rank's ``(T, d)`` token shard; ``params`` is the
    *replicated* stacked-expert pytree from :func:`init_moe` (so the DP
    param-averaging recipe applies unchanged) — each rank computes only its
    ``n_experts/size`` experts on tokens collected from every rank.
    Returns ``(y, aux)``: ``y[t]`` is the gated expert output (zeros for
    capacity-dropped tokens — add the residual outside), ``aux`` the
    load-balancing loss."""
    from .tp import shard_axis

    size = comm.size
    T, d = x.shape
    E = params["gate"].shape[1]
    if E % size != 0:
        raise ValueError(
            f"n_experts ({E}) not divisible by world size ({size})")
    epr = E // size
    C = capacity

    dispatch, combine, aux = top1_route(x @ params["gate"], C)

    # (T, d) x (T, E, C) -> per-expert slot buffers, experts rank-major.
    send = jnp.einsum("td,tec->ecd", x, dispatch)
    send = send.reshape(size, epr * C, d)

    if size > 1:
        # Rank s keeps row s of the source-concatenated axis 1: its experts'
        # slot buffers from every source rank.
        recv = comm.Alltoall(send, gatheraxis=1, scatteraxis=0, numelem=1)
        recv = recv.reshape(size, epr, C, d).transpose(1, 0, 2, 3)
    else:
        recv = send.reshape(1, epr, C, d).transpose(1, 0, 2, 3)
    xin = recv.reshape(epr, size * C, d)

    w1 = shard_axis(comm, params["w1"], 0)
    b1 = shard_axis(comm, params["b1"], 0)
    w2 = shard_axis(comm, params["w2"], 0)
    b2 = shard_axis(comm, params["b2"], 0)
    h = activation(jnp.einsum("esd,edf->esf", xin, w1) + b1[:, None, :])
    yout = jnp.einsum("esf,efd->esd", h, w2) + b2[:, None, :]

    # Inverse exchange: the same Alltoall pattern returns each token's
    # expert output to its owner (the exchange is its own inverse layout).
    back = yout.reshape(epr, size, C, d).transpose(1, 0, 2, 3)
    back = back.reshape(size, epr * C, d)
    if size > 1:
        mine = comm.Alltoall(back, gatheraxis=1, scatteraxis=0, numelem=1)
        mine = mine.reshape(E, C, d)
    else:
        mine = back.reshape(E, C, d)

    # Bias must only reach tokens that actually occupied a slot: empty slots
    # carry b2 after the expert FFN, and combine's zero rows remove them.
    y = jnp.einsum("ecd,tec->td", mine, combine)
    return y, aux


def balanced_assignment(loads, size: int):
    """A load-balancing expert assignment with equal per-rank counts:
    experts sorted by observed load descending, dealt to the ranks in
    snake order (forward, then backward, ...), so each rank gets
    ``E/size`` experts and the per-rank load totals stay within one
    expert of each other.  Returns the permutation ``perm`` consumed by
    :func:`rebalance_experts`: new global slot ``u`` (rank-major,
    ``u // epr`` = owner) holds old expert ``perm[u]``."""
    loads = [float(x) for x in jnp.asarray(loads).reshape(-1)]
    E = len(loads)
    if E % size:
        raise ValueError(
            f"n_experts ({E}) not divisible by world size ({size})")
    epr = E // size
    order = sorted(range(E), key=lambda e: -loads[e])
    slots = [[] for _ in range(size)]
    it = iter(order)
    for k in range(epr):
        ranks = range(size) if k % 2 == 0 else range(size - 1, -1, -1)
        for r in ranks:
            slots[r].append(next(it))
    return tuple(e for r in range(size) for e in slots[r])


def rebalance_experts(comm, experts, assignment, strategy=None):
    """Expert rebalancing as a planned redistribution
    (:mod:`mpi4torch_tpu.reshard`): ``experts`` is a pytree of
    expert-stacked arrays whose axis 0 holds this rank's LOCAL experts
    (``epr`` per rank, rank-major — the persistent EP sharding), and
    ``assignment`` is a permutation of the ``E`` global experts (e.g.
    from :func:`balanced_assignment`): new global slot ``u`` receives
    old expert ``assignment[u]``.

    Every leaf rides one block-permutation plan — a single
    ``collective_permute`` round per moving expert in flight, never a
    full gather — and the move is differentiable: cotangents ride the
    inverse permutation back to the old owners."""
    from .. import reshard as _rs

    size = comm.size
    assignment = tuple(int(a) for a in assignment)

    def one(x):
        lay = _rs.Layout((size,), ((0,),) + ((),) * (jnp.ndim(x) - 1))
        return _rs.reshard_blocks(comm, x, lay, 0, assignment,
                                  strategy=strategy)

    return jax.tree.map(one, experts)


def moe_ffn_dense(x, params: Dict[str, Any], capacity: int,
                  activation=jax.nn.gelu):
    """Single-device oracle: identical routing/capacity semantics, all
    experts local.  Distributed and dense paths must agree token-for-token
    (the EP correctness contract the tests pin down)."""
    dispatch, combine, aux = top1_route(x @ params["gate"], capacity)
    buf = jnp.einsum("td,tec->ecd", x, dispatch)
    h = activation(jnp.einsum("ecd,edf->ecf", buf, params["w1"])
                   + params["b1"][:, None, :])
    yout = jnp.einsum("ecf,efd->ecd", h, params["w2"]) + params["b2"][:, None, :]
    y = jnp.einsum("ecd,tec->td", yout, combine)
    return y, aux


# ---------------------------------------------------------------------------
# Held-share top-k layer (sigmoid scores, swiglu experts, shared expert)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class Experts:
    """A top-k expert FFN as one rank sees it: ``n_experts`` routed
    experts of width ``d_expert`` in the model, ``top_k`` per token; this
    rank holds experts ``first_expert`` to ``first_expert + n_held - 1``.
    The scores are ``score`` (``sigmoid`` | ``softmax``) of the router's
    outputs; the chosen ones are renormalised over the ``top_k`` chosen
    where ``renorm`` says so, and scaled by ``scale``; ``n_shared``
    shared experts (one swiglu of width ``n_shared * d_expert``) are
    passed by every token.  ``n_zero`` zero-compute experts stand behind
    the routed ones (router outputs ``n_experts`` to ``n_experts + n_zero
    - 1``): each returns its input, so a token that chooses some of them
    adds ``(sum of their weights) * x`` and computes that many fewer
    experts.  No rank holds them: a token's own rank adds their part.

    ``latent > 0`` puts the routed experts in a latent of that width:
    one down-projection ``(d, latent)`` before them and one
    up-projection ``(latent, d)`` behind them, shared by all of them;
    an expert's matrices are ``latent`` wide on the stream's side and
    the weighted sum over a token's experts is taken in the latent.  The
    router and the shared expert read the stream itself.  ``act`` is an
    expert's form, the shared one's too: ``"swiglu"`` (``W2 (silu(Wg x)
    * Wu x)``, ``w1 = [gate | up]``), ``"reglu"`` (``W2 (relu(Wg x) * Wu
    x)``, the same leaves) or ``"relu2"`` (``W2 relu(W1 x)^2``, no
    gate).  ``d_shared`` is the shared expert's width where it is not
    ``n_shared * d_expert``.

    Under an expert-parallel communicator of ``R`` ranks
    (:func:`exchanged_experts_ffn`) the spec is every rank's alike:
    ``n_held = n_experts / R``, ``first_expert = 0``, and rank ``r``
    holds experts ``r * n_held`` to ``(r + 1) * n_held - 1``."""
    n_experts: int
    top_k: int
    d_expert: int
    first_expert: int
    n_held: int
    n_shared: int = 0
    scale: float = 1.0
    score: str = "sigmoid"
    renorm: bool = True
    n_zero: int = 0
    latent: int = 0
    act: str = "swiglu"
    d_shared: int = 0

    @property
    def width(self) -> int:
        """The router's outputs: routed and zero-compute experts."""
        return self.n_experts + self.n_zero

    @property
    def shared_width(self) -> int:
        return self.d_shared or self.n_shared * self.d_expert

    def __post_init__(self):
        if self.score not in ("sigmoid", "softmax"):
            raise ValueError(f"unknown score function {self.score!r}")
        if self.act not in _EXPERT:
            raise ValueError(f"unknown expert activation {self.act!r}")
        if self.n_zero < 0:
            raise ValueError(f"n_zero={self.n_zero} must be >= 0")
        if self.latent < 0 or (self.latent and self.n_zero):
            raise ValueError(
                f"latent={self.latent} must be >= 0, and a zero-compute "
                "expert returns its input, which a latent layer's experts "
                "never see at the stream's width")
        if self.d_shared and not self.n_shared:
            raise ValueError("d_shared is the width of a shared expert: "
                             "it needs n_shared > 0")
        if not 0 < self.top_k <= self.width:
            raise ValueError(
                f"top_k={self.top_k} must lie in [1, n_experts + n_zero="
                f"{self.width}]")
        last = self.first_expert + self.n_held
        if self.first_expert < 0 or self.n_held < 1 \
                or last > self.n_experts:
            raise ValueError(
                f"held experts [{self.first_expert}, {last}) must be a "
                f"non-empty part of [0, {self.n_experts})")


def init_experts(key, spec: Experts, d_model: int,
                 dtype=jnp.float32) -> Dict[str, Any]:
    """Parameters of one held share: the router at its full width
    (``spec.width``: zero-compute experts have an output each and no
    other leaf), a selection ``bias`` (zeros; it takes no gradient), the
    held experts' ``w1`` (fused ``[gate | up]`` for swiglu and reglu) and
    ``w2``,
    the shared expert, and a latent layer's ``down`` and ``up``."""
    kr, k1, k2, k3, k4 = jax.random.split(key, 5)
    f, e = spec.d_expert, spec.n_held
    fan = 1 if spec.act == "relu2" else 2
    d_in = spec.latent or d_model

    def dense(key, *shape):
        return jax.random.normal(key, shape, dtype) / jnp.sqrt(
            jnp.asarray(shape[-2], dtype))

    p = {"router": dense(kr, d_model, spec.width),
         "bias": jnp.zeros((spec.width,), dtype),
         "w1": dense(k1, e, d_in, fan * f), "w2": dense(k2, e, f, d_in)}
    if spec.n_shared:
        p["shared_w1"] = dense(k3, d_model, fan * spec.shared_width)
        p["shared_w2"] = dense(k4, spec.shared_width, d_model)
    if spec.latent:
        # Keys of their own: the five above stay what they were.
        k5, k6 = jax.random.split(jax.random.fold_in(key, 5))
        p["down"] = dense(k5, d_model, spec.latent)
        p["up"] = dense(k6, spec.latent, d_model)
    return p


def route_topk(x, router, bias, top_k: int, scale: float, *,
               score: str = "sigmoid", renorm: bool = True):
    """Scores in at least float32 over all the router's outputs
    (``score``: each output's ``sigmoid``, or the ``softmax`` over them),
    the ``top_k`` largest of ``score + bias`` chosen (the bias steers the
    choice and takes no gradient), weights ``scale * score``, over the
    sum of the chosen scores where ``renorm``.  Returns ``(chosen (T, k)
    int32, weights (T, k))``."""
    ct = jnp.promote_types(x.dtype, jnp.float32)
    squash = jax.nn.sigmoid if score == "sigmoid" else \
        functools.partial(jax.nn.softmax, axis=-1)
    score = squash(jnp.matmul(
        x.astype(ct), router.astype(ct),
        precision=jax.lax.Precision.HIGHEST))
    _, chosen = jax.lax.top_k(
        score + jax.lax.stop_gradient(bias.astype(ct)), top_k)
    picked = jnp.take_along_axis(score, chosen, axis=-1)
    if not renorm:
        return chosen, scale * picked
    return chosen, scale * picked / jnp.sum(picked, axis=-1, keepdims=True)


# A prefix of the sorted pairs is whole row tiles of the grouped product;
# below _MIN_PAIRS pairs (a decode step's 128-2,816) the layer keeps its
# one buffer of all of them: nothing there is worth a second body.
_ROW_TILE = 512
_MIN_PAIRS = 4096


def _prefix_rows(pairs: int, spec: Experts) -> int:
    """``B``: the rows of the sorted pairs that the layer works on before
    it asks whether any are left: twice the held experts' even share of
    ``pairs`` in whole row tiles, ``pairs`` itself where that is no less
    or where ``pairs`` is under :data:`_MIN_PAIRS`."""
    if pairs < _MIN_PAIRS:
        return pairs
    even = -(-2 * pairs * spec.n_held // spec.n_experts)
    return min(pairs, -(-even // _ROW_TILE) * _ROW_TILE)


@jax.custom_vjp
def _permute_rows(x, perm, inverse):
    """``x[perm]`` for a permutation: the adjoint is the gather by the
    inverse, not the scatter-add a general gather transposes to."""
    return x[perm]


_permute_rows.defvjp(
    lambda x, perm, inverse: (x[perm], (perm, inverse)),
    lambda res, g: (g[res[1]], None, None))


@jax.custom_vjp
def _pair_rows(x, order, inverse, keep):
    """The (token, choice) pairs of ``x`` ``(T, d)`` in sorted order, as
    rows: row ``r`` is token ``order[r] // k``, zero where ``keep`` is
    not set.  No ``(T * k, d)`` copy in token order is made on the way
    in; on the way back the kept rows' cotangents are unsorted by the
    inverse permutation and summed over a token's ``k`` pairs."""
    k = order.shape[0] // x.shape[0]
    return jnp.where(keep, x[order // k], 0)


def _pair_rows_bwd(res, g):
    order, inverse, keep, T = res
    g = jnp.where(keep, g, 0)[inverse]
    return (jnp.sum(g.reshape(T, -1, g.shape[-1]), axis=1, dtype=g.dtype),
            None, None, None)


_pair_rows.defvjp(
    lambda x, order, inverse, keep: (
        _pair_rows(x, order, inverse, keep),
        (order, inverse, keep, x.shape[0])),
    _pair_rows_bwd)


# The way back reads its table in column pieces of at most this many
# bytes.  XLA's row gather on a v5e turns five times slower a row once
# its table passes some 120 MB (131,072 rows of 2,304 bf16 from a table
# of 113 MB: 1.0 ms, of 132 MB: 5.0 ms), and a prefill's 60 MiB table
# read 15% faster in two pieces than whole; pieces of 16 MiB read slower
# again (PERF.md section 6, PR 42).
_GATHER_PIECE_BYTES = 48 * 2 ** 20


def _column_pieces(table):
    """``table`` ``(rows, d)`` as column slices of whole lane tiles, each
    of at most :data:`_GATHER_PIECE_BYTES` (itself where it is no more)."""
    rows, d = table.shape
    pieces = -(-rows * d * table.dtype.itemsize // _GATHER_PIECE_BYTES)
    step = -(-d // (pieces * 128)) * 128
    return [table] if step >= d else [
        table[:, at:at + step] for at in range(0, d, step)]


def _sum_by_choice(table, at, weight=None):
    """``(T, d)``: ``sum over j of table[at[t, j]]`` (``* weight[t, j]``,
    in ``weight``'s type), a pair whose ``at`` ``(T, k)`` names no row of
    the table adding nothing.  The rows are gathered choice-major,
    ``(k, T, d)``, so that the sum runs down the major axis where ``(T,
    k, d)`` would first be laid out anew (eight rows to a tile of
    sixteen)."""
    rows, (T, k) = table.shape[0], at.shape
    at = at.T.reshape(-1)
    inside = ((at >= 0) & (at < rows))[:, None]
    at = jnp.clip(at, 0, rows - 1)
    sums = []
    for piece in _column_pieces(table):
        got = jnp.where(inside, piece[at], 0).reshape(k, T, -1)
        sums.append(
            jnp.sum(got, axis=0, dtype=table.dtype) if weight is None else
            jnp.sum(got.astype(weight.dtype) * weight.T[..., None], axis=0))
    return jnp.concatenate(sums, axis=-1)


@jax.custom_vjp
def _part_rows(x, order, at, keep):
    """:func:`_pair_rows` for a part of the sorted pairs: ``order``, the
    part's pairs; ``at`` ``(T, k)``, each pair's row of the part (outside
    ``[0, len(order))`` for a pair of another part).  The adjoint reads
    the part's cotangents as a table at ``at``: as many rows as the part
    has, not ``T * k``."""
    return jnp.where(keep, x[order // at.shape[1]], 0)


_part_rows.defvjp(
    lambda x, order, at, keep: (_part_rows(x, order, at, keep), (at, keep)),
    lambda res, g: (_sum_by_choice(jnp.where(res[1], g, 0), res[0]),
                    None, None, None))


@jax.custom_vjp
def _weighted_back(ys, weight, order, at):
    """A part's rows ``ys`` back at their tokens: ``(T, d)`` in
    ``weight``'s type, ``sum over j of weight[t, j] * ys[at[t, j]]``
    over the pairs that lie in the part.  The adjoint gathers
    ``len(order)`` rows of the cotangent (a row's token is ``order[r] //
    k``) and never makes the ``(T * k, d)`` cotangent of the rows."""
    return _sum_by_choice(ys, at, weight)


def _weighted_back_bwd(res, g):
    ys, weight, order, at = res
    g_rows = g[order // weight.shape[1]]
    g_ys = (g_rows * weight.reshape(-1)[order][:, None]).astype(ys.dtype)
    g_weight = jnp.sum(g_rows * ys.astype(g.dtype), axis=-1)
    inside = (at >= 0) & (at < ys.shape[0])
    g_weight = jnp.where(inside, g_weight[jnp.clip(at, 0, ys.shape[0] - 1)],
                         0)
    return g_ys, g_weight.astype(weight.dtype), None, None


_weighted_back.defvjp(
    lambda ys, weight, order, at: (_weighted_back(ys, weight, order, at),
                                   (ys, weight, order, at)),
    _weighted_back_bwd)


def _swiglu(x, w1, w2, dot):
    gate, up = jnp.split(dot(x, w1), 2, axis=-1)
    return dot(jax.nn.silu(gate) * up, w2)


def _reglu(x, w1, w2, dot):
    gate, up = jnp.split(dot(x, w1), 2, axis=-1)
    return dot(jax.nn.relu(gate) * up, w2)


def _relu2(x, w1, w2, dot):
    return dot(jnp.square(jax.nn.relu(dot(x, w1))), w2)


_EXPERT = {"swiglu": _swiglu, "reglu": _reglu, "relu2": _relu2}


def _sorted_part(act, lo, n, x, w1, w2, weight, order, inverse, kept, rows):
    """The ``n`` sorted pairs from row ``lo`` on (``lo`` may be traced)
    through the held experts and back at their tokens: ``(T, d)`` in
    ``weight``'s type, ``sum over a token's pairs in this part of weight
    * E(x)``.  ``rows``: the held experts' group sizes over all the
    sorted pairs, of which this part's products take what lies in it;
    ``kept`` ``(pairs, 1)`` marks the sorted rows that are a held
    expert's.  Every buffer has ``n`` rows."""
    ends = jnp.cumsum(rows)
    sizes = jnp.clip(ends, lo, lo + n) - jnp.clip(ends - rows, lo, lo + n)
    order = jax.lax.dynamic_slice_in_dim(order, lo, n)
    keep = jax.lax.dynamic_slice_in_dim(kept, lo, n)
    at = inverse.reshape(weight.shape) - lo
    xs = _part_rows(x, order, at, keep)
    grouped = lambda a, w: jax.lax.ragged_dot(a, w, sizes)
    ys = jnp.where(keep, _EXPERT[act](xs, w1, w2, grouped), 0)
    return _weighted_back(ys, weight, order, at)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _prefix_then_rest(act, prefix, diff, *where):
    """:func:`_sorted_part` of all the sorted pairs, ``diff = (x, w1,
    w2, weight)`` and ``where = (order, inverse, kept, rows)``, the
    first ``prefix`` rows always and the rows behind them only where a
    held expert's row lies there.

    Where nothing is differentiated (a serving program) that is ONE body
    in a loop, ``prefix`` rows at a time while a held row is left: one
    turn unless the held rows overflow the prefix, and a program with
    many calls (a long prompt's pieces) holds no second pair of grouped
    products a call, whose code a start-up would load.  Under a gradient
    the prefix is taken by itself and the rest whole under one
    ``jax.lax.cond``, forward and backward, so that the backward takes
    the prefix's products from what the forward kept and the branch not
    taken costs nothing: the rest's cotangents are added to the prefix's
    inside the condition, not handed out as arrays of zeros to be added
    to them."""
    order, inverse, kept, rows = where
    pad = -order.shape[0] % prefix
    padded = (jnp.pad(order, (0, pad)), inverse,
              jnp.pad(kept, ((0, pad), (0, 0))), rows)
    held = jnp.sum(rows)

    def turn(i, y):
        return jax.lax.cond(
            i * prefix < held, lambda y: y + _sorted_part(
                act, i * prefix, prefix, *diff, *padded), lambda y: y, y)

    x, _, _, weight = diff
    return jax.lax.fori_loop(
        0, (order.shape[0] + pad) // prefix, turn,
        jnp.zeros(x.shape, weight.dtype))


def _prefix_then_rest_fwd(act, prefix, diff, *where):
    y, pull = jax.vjp(
        lambda *d: _sorted_part(act, 0, prefix, *d, *where), *diff)
    rest = where[0].shape[0] - prefix
    y = jax.lax.cond(
        jnp.sum(where[3]) > prefix,
        lambda y, *d: y + _sorted_part(act, prefix, rest, *d, *where),
        lambda y, *d: y, y, *diff)
    return y, (pull, diff, where)


def _prefix_then_rest_bwd(act, prefix, res, g):
    pull, diff, where = res
    rest = lambda *d: _sorted_part(
        act, prefix, where[0].shape[0] - prefix, *d, *where)
    grads = jax.lax.cond(
        jnp.sum(where[3]) > prefix, lambda grads, *d: jax.tree.map(
            jnp.add, grads, jax.vjp(rest, *d)[1](g)),
        lambda grads, *d: grads, pull(g), *diff)
    return (grads,) + (None,) * len(where)


_prefix_then_rest.defvjp(_prefix_then_rest_fwd, _prefix_then_rest_bwd)


def held_experts_ffn(x, params: Dict[str, Any], spec: Experts,
                     comm_ep=None, live=None, routing=None):
    """The held experts' part of the layer for ``x`` ``(T, d)``, plus the
    shared expert and the zero-compute experts: ``sum over chosen and
    held e of w_e E_e(x) + E_shared(x) + (sum over chosen zero-compute z
    of w_z) x``; of a latent layer (``spec.latent``) ``(sum over chosen
    and held e of w_e E_e(x W_down)) W_up + E_shared(x)``, the sum taken
    in the latent.  The weights are taken (and, where the spec says so,
    renormalised) over all ``top_k`` chosen experts, held or not; what
    the experts held elsewhere would add is left out.  The zero-compute
    part is whole: every token of ``x`` is at home here.

    Every (token, chosen expert) pair is a row; the rows of held experts
    are sorted by expert to the front and are the groups of two grouped
    products (``jax.lax.ragged_dot``).  The layer works on a prefix of
    the sorted pairs, ``B`` rows (:func:`_prefix_rows`: twice the held
    experts' even share, ``2 * top_k * T * n_held / n_experts``, in
    whole tiles of :data:`_ROW_TILE` rows; a Python int from shapes and
    the spec alone): the rows gathered in, both products' row operands
    and the mask have ``B`` rows, and the weighted sum back at the
    tokens reads that table at all ``top_k * T`` pairs.  No row routed
    to a held expert is ever dropped: where the held rows are more than
    ``B`` (a router that collapsed onto the held experts) the rows
    behind the prefix go through the same two products with the group
    sizes less what the prefix took, under a ``jax.lax.cond`` on
    ``sum(rows) > B`` whose other branch runs nothing, forward and
    backward; a program that takes no gradient holds one body and runs
    it a prefix's rows at a time while a held row is left
    (:func:`_prefix_then_rest`).  Where ``B`` comes to ``top_k
    * T`` (half or more of the experts held) or the pairs are fewer
    than :data:`_MIN_PAIRS` (a decode step) there is one buffer of all
    the pairs and no condition; the rows behind the groups belong to no
    group and take none of the products' time (the kernel walks the
    groups' tiles).

    ``live`` (``(T,)`` bool, optional) marks the tokens that are
    somebody's: the others' pairs join the rows behind the groups, take
    no expert's time and are not counted (a serving decode step's free
    slots; their ``y`` rows are the shared expert's alone).

    ``routing`` (optional) is ``(chosen, weight)`` as :func:`route_topk`
    gives them, taken by the caller from rows other than ``x`` (a layer
    whose router reads its input before the mixer); without it the
    router reads ``x``.

    ``comm_ep`` of more than one rank makes this the whole layer: every
    rank's rows go to the experts' owners and back
    (:func:`exchanged_experts_ffn`; ``overflow`` is then 1 where a round
    ran behind the first).

    Returns ``(y, rows, zero_pairs, overflow)``: ``rows`` ``(n_held,)``,
    the rows each held expert took, which are the group sizes the
    products are handed (cut at ``B`` between the prefix and the rest);
    ``zero_pairs``, the live (token, choice) pairs that chose a
    zero-compute expert (int32; the number 0 where the spec has none);
    ``overflow``, int32, 1 where this call took the branch for the rows
    behind the prefix (the counter ``moe_overflow_calls`` sums it).
    Inference runs the same code: a compiled prefill or decode step of
    ``mpi4torch_tpu.serve`` calls it on its rows and hands the counts
    out with the step's record."""
    y, c = experts_ffn(x, params, spec, comm_ep, live, routing)
    return y, c["rows"], c["zero_pairs"], c["overflow"]


def experts_ffn(x, params: Dict[str, Any], spec: Experts, comm_ep=None,
                live=None, routing=None):
    """:func:`held_experts_ffn` with its counts by name, ``(y, {"rows",
    "zero_pairs", "overflow"})``, and the ONE place that decides between
    a rank's held share and the exchange: over an expert-parallel
    communicator of more than one rank the counts are
    :func:`exchanged_experts_ffn`'s, ``rounds``, ``sent`` and ``padding``
    among them."""
    if comm_ep is not None and comm_ep.size > 1:
        if live is not None:
            raise CommError(
                "held_experts_ffn: the exchange over an expert-parallel "
                f"communicator (size {comm_ep.size}) is the training "
                "path's; a serving step's free slots (live) are not "
                "exchanged yet")
        return exchanged_experts_ffn(x, params, spec, comm_ep, routing)
    y, rows, zero_pairs, overflow = _held_share(x, params, spec, live,
                                                routing)
    return y, {"rows": rows, "zero_pairs": zero_pairs, "overflow": overflow}


def _held_share(x, params, spec: Experts, live, routing):
    k, held = spec.top_k, spec.n_held
    chosen, weight = routing or route_experts(x, params, spec)
    local = chosen.reshape(-1) - spec.first_expert
    here = (local >= 0) & (local < held)
    if live is not None:
        here &= jnp.repeat(live, k)
    group = jnp.where(here, local, held)
    order = jnp.argsort(group, stable=True)
    inverse = jnp.zeros_like(order).at[order].set(
        jnp.arange(order.shape[0], dtype=order.dtype))
    rows = jnp.sum(jax.nn.one_hot(group, held, dtype=jnp.int32), axis=0)
    kept = (group[order] < held)[:, None]

    expert = _EXPERT[spec.act]
    xin = x @ params["down"] if spec.latent else x
    pairs = order.shape[0]
    prefix = _prefix_rows(pairs, spec)
    if prefix == pairs:
        # One buffer of all the pairs, as before there was a prefix.
        xs = _pair_rows(xin, order, inverse, kept)
        grouped = lambda a, w: jax.lax.ragged_dot(a, w, rows)
        ys = jnp.where(kept, expert(xs, params["w1"], params["w2"], grouped),
                       0)
        ys = _permute_rows(ys, inverse, order).reshape(-1, k, xs.shape[-1])
        y = jnp.sum(ys.astype(weight.dtype) * weight[..., None], axis=1)
        overflow = jnp.zeros((), jnp.int32)
    else:
        y = _prefix_then_rest(
            spec.act, prefix, (xin, params["w1"], params["w2"], weight),
            order, inverse, kept, rows)
        overflow = (jnp.sum(rows) > prefix).astype(jnp.int32)
    y, zero_pairs = _at_home(x, y, chosen, weight, params, spec, live)
    return y, rows, zero_pairs, overflow


def _at_home(x, y, chosen, weight, params, spec: Experts, live=None):
    """What a token's own rank adds to the routed experts' weighted sum
    ``y``, in ``weight``'s type: the zero-compute experts' part, a
    latent layer's up-projection and the shared expert.  Returns ``(y``
    in ``x``'s type, the live pairs that chose a zero-compute
    expert``)``."""
    zero_pairs = 0
    if spec.n_zero:
        is_zero = chosen >= spec.n_experts
        y = y + jnp.sum(jnp.where(is_zero, weight, 0), axis=1,
                        keepdims=True) * x.astype(weight.dtype)
        if live is not None:
            is_zero &= live[:, None]
        zero_pairs = jnp.sum(is_zero, dtype=jnp.int32)
    y = y.astype(x.dtype)
    if spec.latent:
        y = y @ params["up"]
    if spec.n_shared:
        y = y + _EXPERT[spec.act](x, params["shared_w1"],
                                  params["shared_w2"], jnp.matmul)
    return y, zero_pairs


# ---------------------------------------------------------------------------
# The same layer over an expert-parallel communicator: the rows' exchange
# ---------------------------------------------------------------------------

# A round's buffer holds this many times a destination's even share of a
# rank's pairs (in whole row tiles).  Two readings on four v5e chips, both
# under a routing that the seeded weights hold even (largest chip 1.009 of
# the mean): a step of 2,176 ms at 1.25 and of 2,783 ms at 2.0, no round
# behind the first in either (PERF.md section 6, PR 48).  Under an even
# routing the smaller buffer wins by its padding alone; what the later
# rounds of an uneven one cost against it was not measured.
_EXCHANGE_ROOM = 1.25


def _exchange_rows(pairs: int, size: int) -> int:
    """``C``: the rows of a round's buffer for ONE destination:
    :data:`_EXCHANGE_ROOM` times the even share ``pairs / size`` in whole
    tiles of :data:`_ROW_TILE` rows, ``pairs`` itself where that is no
    less.  ``ceil(pairs / C)`` rounds are always enough: a rank has
    ``pairs`` rows in all."""
    room = -(-int(_EXCHANGE_ROOM * pairs) // size)
    return min(pairs, -(-room // _ROW_TILE) * _ROW_TILE)


def route_experts(x, params: Dict[str, Any], spec: Experts):
    """:func:`route_topk` of ``x`` ``(T, d)`` under ``spec``: ``(chosen,
    weight)``, what :func:`held_experts_ffn` takes as ``routing``."""
    return route_topk(x, params["router"], params["bias"], spec.top_k,
                      spec.scale, score=spec.score, renorm=spec.renorm)


def _first_then_rest(part, diff, needed, where):
    """``sum over j < needed of part(j, *diff, *where)`` for a traced
    ``needed >= 1`` that is the same on every rank: part 0 always, the
    later ones in a loop of ``needed - 1`` turns over ONE body, forward
    and backward, as :func:`_prefix_then_rest` takes its rest: the
    backward has part 0's products from what the forward kept and runs a
    later part's forward and backward inside its turn, and a part not
    taken costs nothing.  ``part`` takes its number traced and may hold
    collectives: every rank makes the same turns."""

    def rest(y, diff, needed, where):
        return jax.lax.fori_loop(
            1, needed, lambda j, y: y + part(j, *diff, *where), y)

    @jax.custom_vjp
    def run(diff, needed, where):
        return rest(part(0, *diff, *where), diff, needed, where)

    def fwd(diff, needed, where):
        y, pull = jax.vjp(lambda *d: part(0, *d, *where), *diff)
        return rest(y, diff, needed, where), (pull, diff, needed, where)

    def bwd(res, g):
        pull, diff, needed, where = res
        grads = jax.lax.fori_loop(
            1, needed, lambda j, grads: jax.tree.map(
                jnp.add, grads,
                jax.vjp(lambda *d: part(j, *d, *where), *diff)[1](g)),
            pull(g))
        return grads, None, jax.tree.map(lambda _: None, where)

    run.defvjp(fwd, bwd)
    return run(diff, needed, where)


def _place_in_group(group, groups: int):
    """``(place, counts)``: how many earlier entries of ``group`` ``(n,)``
    (ints in ``[0, groups)``) share each entry's group, and the groups'
    sizes: what a stable sort by group needs, without a sort (a sort of
    100,000 keys takes the v5e's compiler 20 s an instance).  The running
    counts are products with a triangle of ones, a block of
    :data:`_ROW_TILE` entries at a time: exact, the counts staying under
    2**24."""
    n = group.shape[0]
    blk = min(_ROW_TILE, n)
    pad = -n % blk
    hot = jax.nn.one_hot(jnp.pad(group, (0, pad), constant_values=groups),
                         groups, dtype=jnp.bfloat16).reshape(-1, blk, groups)
    inner = jnp.einsum("ij,bjg->big", jnp.tril(jnp.ones((blk, blk), hot.dtype)),
                       hot, preferred_element_type=jnp.float32)
    sums = inner[:, -1, :]
    before = jnp.matmul(
        jnp.tril(jnp.ones((sums.shape[0],) * 2, sums.dtype), -1), sums,
        precision=jax.lax.Precision.HIGHEST)
    running = (inner + before[:, None, :]).reshape(-1, groups)[:n]
    place = jnp.take_along_axis(
        running, jnp.clip(group, 0, groups - 1)[:, None], axis=1)[:, 0] - 1
    return place.astype(jnp.int32), \
        (before[-1] + sums[-1]).astype(jnp.int32)


def _by_sender(w, senders: int):
    """An owner's ``(held, m, n)`` matrices as the groups of a buffer that
    lies sender by sender: ``(senders * (held + 1), m, n)``, each sender's
    the held experts' and one of zeros for its tail.  Broadcast, not
    gathered by an index: the adjoint is then a sum over the sender axis
    and no scatter-add, and what a tail's rows give falls into the slot
    of zeros and is left there."""
    w = jnp.pad(w, ((0, 1), (0, 0), (0, 0)))
    return jnp.broadcast_to(w, (senders,) + w.shape).reshape(
        (-1,) + w.shape[1:])


def exchanged_experts_ffn(x, params: Dict[str, Any], spec: Experts, comm_ep,
                          routing=None):
    """The whole top-k layer for this rank's ``x`` ``(T, d)`` over an
    expert-parallel communicator of ``R`` ranks, each of which holds
    ``n_held = n_experts / R`` experts (``params["w1"]``, ``["w2"]``: this
    rank's own, experts ``rank * n_held`` on; the router, and a shared
    expert or a latent's projections, replicated): ``sum over chosen e of
    w_e E_e(x)`` with every expert computed by its owner, plus what
    :func:`held_experts_ffn` adds at a token's home.

    Every rank routes its own tokens over all the experts; its (token,
    choice) pairs, sorted by expert, lie sorted by owner.  A ROUND moves
    at most ``C`` rows to each owner (:func:`_exchange_rows`; a Python
    int from shapes alone): the ``(R, C, d)`` buffer goes out through
    :func:`~mpi4torch_tpu.ops.ragged.ragged_alltoall`, the owner runs
    the two grouped products (``jax.lax.ragged_dot``) over the buffer
    WHERE IT ARRIVED, the rows go back through the same exchange into
    the slots they left from, and the token's rank adds them up under
    their weights.  The counts of each (sender, expert) go ahead once,
    in one small ``Alltoall``, and every place (a pair's in its buffer,
    a group's length at the owner) is arithmetic on running counts: no
    sort, and no row moved at the owner.  A sender's rows arrive sorted
    by expert, so the buffer is, sender by sender, a run of rows a held
    expert and the tail of its ``C`` slots that holds nothing: ``R *
    (held + 1)`` groups that sum to ``R * C`` in every round, against
    the owner's matrices with one slot of zeros for the tails, broadcast
    over the senders (:func:`_by_sender`); a matrix's gradient is summed
    sender by sender by the grouped product and then over the senders.
    No row is ever dropped: round 0 runs always, and the rounds behind
    it in a loop of ``ceil(most rows any rank has for one owner / C) -
    1`` turns over one body, the count all-reduced so that every rank
    makes the same turns, forward and backward
    (:func:`_first_then_rest`); an even routing makes none, a collapsed
    one ``R / 1.25``.  Forward and adjoint move through the facade's
    differentiable ``Alltoall``: an expert's gradient arrives at its
    owner summed over every rank's tokens.

    Returns ``(y, counts)``: ``rows`` ``(n_held,)``, the rows each of
    this rank's experts took from all ranks; ``rounds``, int32, the
    rounds taken behind the first, and ``overflow``, 1 where there was
    one; ``sent`` ``(R,)``, the rows this rank
    sent to each rank (itself among them); ``padding``, the buffer rows
    it sent that held no row; ``zero_pairs`` as
    :func:`held_experts_ffn`."""
    from ..constants import MPI_MAX
    from ..ops.ragged import ragged_alltoall

    size, k, held = comm_ep.size, spec.top_k, spec.n_held
    if spec.first_expert or held * size != spec.n_experts:
        raise ValueError(
            f"over {size} expert-parallel ranks each holds n_experts / "
            f"{size} experts from its own rank's first on: the spec says "
            f"n_held={held} of {spec.n_experts}, first_expert="
            f"{spec.first_expert}")
    chosen, weight = routing or route_experts(x, params, spec)
    xin = x @ params["down"] if spec.latent else x
    pairs = chosen.size
    cap = _exchange_rows(pairs, size)

    # Pairs sorted by expert lie sorted by owner; a zero-compute expert's
    # pairs stay at home, behind them all (group n_experts).
    flat = chosen.reshape(-1)
    group = jnp.minimum(flat, spec.n_experts)
    among, per_expert = _place_in_group(group, spec.n_experts + 1)
    per_expert = per_expert[:-1]
    sent = jnp.sum(per_expert.reshape(size, held), axis=1)
    owner = jnp.minimum(group // held, size - 1)
    # A pair's place among the rows bound for its owner, the owner's
    # rows by expert.
    first = (jnp.cumsum(per_expert) - per_expert)[
        jnp.minimum(group, spec.n_experts - 1)] - (
            jnp.cumsum(sent) - sent)[owner]
    place = jnp.where(group < spec.n_experts, first + among, -1)

    with layer_scope("moe_exchange"):
        # (sender, held expert): what each rank has for my experts.
        taken = comm_ep.Alltoall(per_expert.reshape(size, 1, held),
                                 gatheraxis=1, scatteraxis=0, numelem=1
                                 ).reshape(size, held)
        needed = -(-comm_ep.Allreduce(jnp.max(sent), MPI_MAX) // cap)
    needed = jnp.clip(needed, 1, -(-pairs // cap))
    expert = _EXPERT[spec.act]
    buffer = size * cap

    def one_round(j, xin, w1, w2, weight, place, owner, sent, taken):
        lo = j * cap
        sender = jnp.repeat(jnp.arange(size, dtype=jnp.int32), cap)
        # Where each of my pairs lies in this round's buffer, and back:
        # the pair a buffer row holds (a row that holds none names pair
        # 0 and is masked).
        at = jnp.where((place >= lo) & (place < lo + cap),
                       owner * cap + place - lo, -1)
        pair = jnp.arange(pairs, dtype=jnp.int32)
        rows = jnp.zeros((buffer,), jnp.int32).at[
            jnp.where(at >= 0, at, buffer + pair)].set(
                pair, mode="drop", unique_indices=True)
        slot = jnp.tile(jnp.arange(cap, dtype=jnp.int32), size)
        full = (lo + slot < sent[sender])[:, None]
        at = at.reshape(weight.shape)
        xs = _part_rows(xin, rows, at, full)
        with layer_scope("moe_exchange"):
            got, count = ragged_alltoall(
                comm_ep, xs.reshape(size, cap, -1),
                jnp.clip(sent - lo, 0, cap))
        # A sender's rows arrive sorted by expert, so the buffer as it
        # lies is, sender by sender, one run a held expert and the tail
        # that holds nothing: the grouped products' groups, ``size *
        # (held + 1)`` of them that sum to ``buffer`` in every round.  All
        # of it from the counts that went ahead: which of its rows a
        # sender has in this round, expert by expert.  No mask: a tail's
        # rows came as zeros, meet matrices of zeros, and the way back
        # masks by ``count`` again, forward and adjoint.
        ends = jnp.cumsum(taken, axis=1)
        mine = jnp.clip(ends, lo, lo + cap) - jnp.clip(ends - taken, lo,
                                                       lo + cap)
        sizes = jnp.concatenate([mine, (cap - count)[:, None]],
                                axis=1).reshape(-1)
        # The matrices' copies by sender are made when the rows are here
        # and not ahead of them: left free, the v5e's scheduler makes
        # them early and a step of eight such layers holds 0.9 GB more
        # (9.54 against 8.62 GB of temporaries, compiled for a described
        # v5e 2x2 at SmallThinker's shapes; PERF.md section 6, PR 49).
        got, w1, w2 = jax.lax.optimization_barrier((got, w1, w2))
        ys = expert(got.reshape(buffer, -1), _by_sender(w1, size),
                    _by_sender(w2, size),
                    lambda a, w: jax.lax.ragged_dot(a, w, sizes))
        with layer_scope("moe_exchange"):
            back, _ = ragged_alltoall(comm_ep, ys.reshape(size, cap, -1),
                                      count)
        return _weighted_back(back.reshape(buffer, -1), weight, rows, at)

    y = _first_then_rest(
        one_round, (xin, params["w1"], params["w2"], weight), needed,
        (place, owner, sent, taken))

    y, zero_pairs = _at_home(x, y, chosen, weight, params, spec)
    return y, {"rows": jnp.sum(taken, axis=0), "zero_pairs": zero_pairs,
               "overflow": jnp.minimum(needed - 1, 1), "rounds": needed - 1,
               "sent": sent, "padding": needed * buffer - jnp.sum(sent)}
