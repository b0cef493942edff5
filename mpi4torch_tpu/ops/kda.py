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
  a chunk the WY / UT-transform form as matrix products, across chunks
  the state carried from one to the next.

Which code runs where (``impl="auto"``; :func:`uses_kernel` says which):

* On a TPU, for bfloat16 or float32 heads of whole lanes (``d_k`` and
  ``d_v`` multiples of 128), the forward pass is one Pallas kernel
  (:data:`KERNEL_NAME`): a grid step takes a block of tokens of one head
  where they lie in ``(b, s, h * d)`` and walks it 128 tokens at a time
  (two chunks, their matrices side by side along the lanes); the
  running decay, the two Grams, the inverse of ``I + A``, ``w``, ``u`` and
  the three products with the state live in fast memory for the length
  of a turn, the state itself in scratch for the length of a sequence,
  and only ``o`` is written.  The backward behind its ``custom_vjp``
  keeps the five inputs and nothing else, and is two kernels of its own
  (:data:`BACKWARD_KERNEL_NAMES`) on the same grid.  The first is a
  forward again that writes, not ``o``, the state every turn starts
  from and its chunks' inverses of ``I + A`` (float32; 64 + 32 KB a
  turn, freed when the second has read them).  The second walks the
  token blocks last to first, the adjoint of the state in scratch: a
  turn builds its decay, Grams, ``w`` and ``u'`` again from the inputs
  and the stored state (the forward's chunk code), then their adjoints,
  all in fast memory, and writes the five gradients where the inputs
  lie (``beta``'s a head a row, turned by the wrapper).
* Everywhere else (float64, the CPU, narrower heads), and under
  ``impl="jnp"``: plain JAX with a ``lax.scan`` over the state,
  differentiated by autodiff through the chunked form: the backward is
  the transposed chunk products and a reverse scan, at the price of the
  chunk-level residuals autodiff keeps.  Those are some thirty float32
  arrays of the size of ``g`` (9 GB for 32 heads of 128 over 16,384
  tokens), so heads are taken ``HEAD_GROUP`` at a time, each group
  rematerialised on the way back: one more forward of the chunk
  products for a quarter of the memory.

Both return ``o`` in the state's type (float32 for bfloat16 inputs).

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
token for 8 tokens on end.  The plain path solves ``(I + A) [w | u]``
with ``solve_triangular``; the kernel multiplies by ``(I + A)^-1``,
built by block forward substitution from blocks that double, all by
float32 products at full precision (:func:`_unit_lower_inverse`).

The kernels' backward, for one chunk with ``T = (I + A)^-1``, ``P =
tril(B) scale``, ``u' = u - w S_0``, ``do`` the output's adjoint and
``dS+`` the adjoint of the state the chunk leaves (zero after the last)::

    du' = P^T do + k_out dS+           dP = tril(do u'^T)
    dq_in = do S_0^T                   dk_out = u' dS+^T
    dw = -du' S_0^T                    du = du'
    dS_0 = q_in^T do + diag(e^G_end) dS+ - w^T du'
    y = T^T [dw | du]                  dA = -strict(y_w w^T + y_u u^T)

with ``y`` the adjoint of ``[beta k e^G | beta v]``, then through the two
Grams block by block as they were built (an exponent at the cap passes
nothing to the decay, as ``minimum`` does under autodiff), and ``dg`` the
sums of ``dG`` from each token to its chunk's end.  Products with the
state, its adjoint, ``P`` and the Grams take their operands in the
inputs' type, as the plain path's transposed products are compiled; what
stands in for the solve's adjoint (``y`` and ``dA``) is float32 at full
precision.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from .flash import _on_tpu, dot_precision

CHUNK = 64
HEAD_GROUP = 8
_SUB = 16
_EXP_CAP = 80.0
# The names the kernels carry into traces and HLO: the forward's, then
# the backward's two (its own forward, which keeps a state and the
# chunks' inverses a turn, and the reverse walk).
KERNEL_NAME = "mpi4torch_kda_fwd"
BACKWARD_KERNEL_NAMES = ("mpi4torch_kda_bwd_states", "mpi4torch_kda_bwd_walk")
KERNEL_NAMES = (KERNEL_NAME, *BACKWARD_KERNEL_NAMES)
# Tokens a grid step of the kernel takes at most (a grid step costs
# about 0.35 us whatever it holds: a chunk a step would be bookkeeping
# alone), and the bytes its blocks may take of fast memory: half of
# Mosaic's default scoped limit, the rest is a chunk's temporaries'.
_BLOCK_TOKENS = 1024
_BLOCK_BYTES = 8 << 20
# Tokens the kernel works on together: the chunks whose matrices fit
# side by side in a register's lanes.
_LANES = 128


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


def _kernel_eligible(q, k, v, chunk: int = CHUNK) -> bool:
    """Shapes the kernel takes: ``q``, ``k`` and ``v`` of one type,
    bfloat16 or float32, heads of whole lanes, chunks of whole
    sub-blocks that halve down to one row."""
    return (q.dtype == k.dtype == v.dtype
            and q.dtype in (jnp.bfloat16, jnp.float32)
            and q.shape[-1] % 128 == 0 and v.shape[-1] % 128 == 0
            and chunk % _SUB == 0 and chunk & (chunk - 1) == 0)


def uses_kernel(q, k, v, chunk: int = CHUNK) -> bool:
    """Whether :func:`kda_chunked` (``impl="auto"``) runs its kernels,
    forward and backward."""
    return _kernel_eligible(q, k, v, chunk) and _on_tpu()


def kda_chunked(q, k, v, g, beta, scale=None, chunk: int = CHUNK,
                impl: str = "auto"):
    """The same function as :func:`kda_recurrent` in chunked form.  Any
    sequence length: the tail is padded with tokens that write nothing
    (``beta = 0``) and do not decay (``g = 0``).  ``impl``: ``"auto"``
    (the kernels on a TPU for eligible shapes, else plain JAX),
    ``"pallas"`` (forced; interpreted off the TPU, for tests) or
    ``"jnp"``.  Where the forward is the kernel's the gradient is the
    backward kernels', else autodiff's through the chunked form."""
    if impl not in ("auto", "pallas", "jnp"):
        raise ValueError(f"unknown impl {impl!r}")
    scale = q.shape[-1] ** -0.5 if scale is None else scale
    if impl == "pallas" or (impl == "auto" and uses_kernel(q, k, v, chunk)):
        if not _kernel_eligible(q, k, v, chunk):
            raise ValueError(
                f"the delta rule's kernel takes bfloat16 or float32 heads "
                f"of whole lanes (multiples of 128) in chunks of a power "
                f"of two of at least {_SUB}; got q {q.shape} {q.dtype}, v "
                f"{v.shape} {v.dtype}, chunk {chunk}")
        return _kernel_chunked(q, k, v, g, beta, scale, chunk)
    return _plain_chunked(q, k, v, g, beta, scale, chunk)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _kernel_chunked(q, k, v, g, beta, scale, chunk):
    return _pallas_forward(q, k, v, g, beta, scale, chunk,
                           interpret=not _on_tpu())


def _kernel_chunked_bwd(scale, chunk, inputs, do):
    """The two backward kernels, from the five inputs alone."""
    return _pallas_backward(*inputs, do, scale, chunk,
                            interpret=not _on_tpu())


_kernel_chunked.defvjp(
    lambda q, k, v, g, beta, scale, chunk: (
        _kernel_chunked(q, k, v, g, beta, scale, chunk),
        (q, k, v, g, beta)),
    _kernel_chunked_bwd)


def _head_groups(x):
    """``(b, s, h, ...)`` -> ``(groups, b, s, HEAD_GROUP, ...)``."""
    h = x.shape[2]
    return jnp.moveaxis(x.reshape(
        *x.shape[:2], h // HEAD_GROUP, HEAD_GROUP, *x.shape[3:]), 2, 0)


def _plain_chunked(q, k, v, g, beta, scale, chunk):
    """The chunked rule in plain JAX, ``HEAD_GROUP`` heads at a time,
    each group rematerialised on the way back."""
    h = q.shape[2]
    core = functools.partial(_chunked_heads, scale=scale, chunk=chunk)
    if h <= HEAD_GROUP or h % HEAD_GROUP:
        return core(q, k, v, g, beta)
    o = jax.lax.map(lambda a: jax.checkpoint(core)(*a),
                    tuple(map(_head_groups, (q, k, v, g, beta))))
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


# ---------------------------------------------------------------------------
# The forward kernel
# ---------------------------------------------------------------------------

_NN = (((1,), (0,)), ((), ()))      # a @ b
_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_TN = (((0,), (0,)), ((), ()))      # a.T @ b


def _cumsum_rows(x, period: int):
    """Inclusive sums down the rows of ``x`` ``(n, d)``, starting anew
    every ``period`` rows, by doubling shifts: ``log2 period`` rolls
    along the sublanes."""
    from jax.experimental.pallas import tpu as pltpu

    row = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0) % period
    shift = 1
    while shift < period:
        x = x + jnp.where(row >= shift, pltpu.roll(x, shift, 0), 0)
        shift *= 2
    return x


def _unit_lower_inverse(A, row, col, dot):
    """``(I + A)^-1`` for ``A`` ``(C, C)`` strictly lower triangular, by
    products alone (``dot``, in ``A``'s type at full precision): block
    forward substitution, the blocks doubling.  With ``T`` the inverse
    of the diagonal blocks of ``size`` rows and ``L`` what ``A`` holds
    between the two halves of each block of ``2 size``::

        [[P, 0], [L, Q]]^-1 = [[P^-1, 0], [-Q^-1 L P^-1, Q^-1]]

    is ``T - T L T`` for every block at once (``T L T`` is zero outside
    ``L``'s places).  Blocks of one row are the identity, so the first
    doubling costs nothing: ``2 (log2 C - 1)`` products.  (The sum
    ``(I - X)(I + X^2)(I + X^4)...`` takes as many and is short by four
    digits where a chunk's keys are alike: its terms grow to ``C(15,
    7)`` before they cancel.)"""
    T, size = (row == col).astype(A.dtype) - jnp.where(
        row // 2 == col // 2, A, 0), 2
    while size < A.shape[0]:
        L = jnp.where((row // (2 * size) == col // (2 * size))
                      & (row // size != col // size), A, 0)
        T, size = T - dot(dot(T, L), T), 2 * size
    return T


class _Lanes:
    """``R / C`` chunks' ``(C, C)`` matrices side by side along the
    lanes, ``(C, R)``: an elementwise step or a product then costs what
    it costs for one chunk (a ``(64, 64)`` float32 array fills half of
    each register it takes, and a product's cost goes by the rows pushed
    through the MXU)."""

    def __init__(self, C: int, R: int):
        self.C, self.R, self.side = C, R, R // C
        self.row = jax.lax.broadcasted_iota(jnp.int32, (C, R), 0)
        self.lane = jax.lax.broadcasted_iota(jnp.int32, (C, R), 1)
        self.col = self.lane % C

    def rows(self, j: int) -> slice:
        """Chunk ``j``'s rows of an ``(R, d)`` array."""
        return slice(j * self.C, (j + 1) * self.C)

    def by_lane(self, xs):
        """Chunk ``j``'s ``(n, R)`` array on chunk ``j``'s lanes (an
        iota of its own a piece: Mosaic's layout pass aborts on a slice
        of ``lane``)."""
        out = xs[-1]
        for j in range(self.side - 2, -1, -1):
            out = jnp.where(jax.lax.broadcasted_iota(
                jnp.int32, out.shape, 1) < (j + 1) * self.C, xs[j], out)
        return out

    def own(self, y, j: int):
        """``y`` ``(n, R)`` on chunk ``j``'s lanes, zero on the others'."""
        return jnp.where(jax.lax.broadcasted_iota(
            jnp.int32, y.shape, 1) // self.C == j, y, 0)

    def diagonal(self, y):
        """``(C, R)`` side by side -> ``(R, R)`` down the diagonal: ``X
        Y`` chunk by chunk is ``X`` times ``Y``'s blocks laid so."""
        return jnp.concatenate([self.own(y, j) for j in range(self.side)],
                               axis=0)

    def blocks(self, Y):
        """``(R, R)`` -> its diagonal blocks side by side, ``(C, R)``."""
        return self.by_lane([Y[self.rows(j)] for j in range(self.side)])

    def column(self, x):
        """``(R, 1)``, a value a token -> ``(C, R)``, chunk ``j``'s on
        its lanes, the same along each row."""
        return self.by_lane([jnp.broadcast_to(x[self.rows(j)],
                                              (self.C, self.R))
                             for j in range(self.side)])


class _GramBlock(NamedTuple):
    """The operands of one block of ``_SUB`` rows of every chunk's decay
    Grams: ``rows`` (chunk by chunk ``k up`` and, with a ``q``, ``q
    up``) against ``cols = k down`` ``(R, d_k)``; the two factors, ``ups``
    a chunk and ``down``; ``rising`` (a chunk) and ``falling``, where
    their exponents lie under the cap (the adjoint stops where they do
    not)."""
    rows: jax.Array
    cols: jax.Array
    ups: list
    down: jax.Array
    rising: list
    falling: jax.Array


def _gram_block(q, k, G, r0: int, L: _Lanes) -> _GramBlock:
    """Rows ``r0`` to ``r0 + _SUB`` of every chunk against all its
    columns, both sides measured from the block's middle row.  Columns
    past the block's last row, and other chunks' columns, are computed
    too (capped, so finite or at worst discarded) and fall to the
    callers' masks."""
    C, mid = L.C, r0 + _SUB // 2
    refs = [G[j * C + mid:j * C + mid + 1] for j in range(L.side)]
    fall = jnp.concatenate(
        [jnp.broadcast_to(ref, (C, ref.shape[1])) for ref in refs],
        axis=0) - G
    down = jnp.exp(jnp.minimum(fall, _EXP_CAP))
    rows, ups, rising = [], [], []
    for j, ref in enumerate(refs):
        own = slice(j * C + r0, j * C + r0 + _SUB)
        rise = G[own] - ref
        up = jnp.exp(jnp.minimum(rise, _EXP_CAP))
        rows += [k[own] * up] + ([] if q is None else [q[own] * up])
        ups.append(up)
        rising.append(rise < _EXP_CAP)
    return _GramBlock(jnp.concatenate(rows, axis=0), k * down, ups, down,
                      rising, fall < _EXP_CAP)


class _Turn:
    """What the chunks of one turn do not wait for the state for (the
    decay, the Grams, the inverse of ``I + A``, ``w`` and ``u``), worked
    out for the chunks together.  ``q``, ``k``, ``g`` ``(R, d_k)``, ``v``
    ``(R, d_v)``, ``beta`` ``(R, 1)``, all in the state's type.  Without
    a ``q`` (the backward's own forward) the pairs ``P`` are left out;
    with an ``inv`` (the backward's walk) the inverse is not built."""

    def __init__(self, q, k, v, g, beta, scale, C, mm, prec, inv=None):
        ct = g.dtype
        self.L = L = _Lanes(C, k.shape[0])
        self.mm, self.prec, self.ct = mm, prec, ct
        self.exact = functools.partial(
            jax.lax.dot_general, preferred_element_type=ct,
            precision=jax.lax.Precision.HIGHEST)
        self.G = G = _cumsum_rows(g, C)
        per = 1 if q is None else 2
        self.grams = [_gram_block(q, k, G, r0, L)
                      for r0 in range(0, C, _SUB)]
        kk, qk = [], []
        for gram in self.grams:
            blk = self.dot(gram.rows, gram.cols, _NT)
            pieces = [blk[i * _SUB:(i + 1) * _SUB]
                      for i in range(per * L.side)]
            kk.append(L.by_lane(pieces[0::per]))
            qk.append(L.by_lane(pieces[1::per]) if per == 2 else None)
        self.kk = jnp.concatenate(kk, axis=0)
        self.beta = L.column(beta)
        A = jnp.where(L.row > L.col, self.beta * self.kk, 0)
        self.pairs = None if q is None else jnp.where(
            L.row >= L.col, jnp.concatenate(qk, axis=0), 0) * scale
        # (I + A)^-1 applied to beta k exp(G) and to beta v: the UT
        # transform.
        self.inv = _unit_lower_inverse(
            A, L.row, L.col, lambda x, y: self.exact(
                x, L.diagonal(y), _NN)) if inv is None else inv
        self.wide = L.diagonal(self.inv)
        self.eG = jnp.exp(G)
        self.w = self.exact(self.wide, beta * k * self.eG, _NN)
        self.us = self.exact(self.wide, beta * v, _NN)
        ends = [G[(j + 1) * C - 1:(j + 1) * C] for j in range(L.side)]
        # the state decays by ``decay`` over a chunk, and k_out = k fade
        # writes the chunk's end
        self.decay = [jnp.exp(end) for end in ends]
        self.fade = [jnp.exp(ends[j] - G[L.rows(j)]) for j in range(L.side)]
        self.k_out = [k[L.rows(j)] * self.fade[j] for j in range(L.side)]

    def dot(self, x, y, dims):
        """A product in the inputs' type, summed in the state's."""
        return jax.lax.dot_general(
            x.astype(self.mm), y.astype(self.mm), dims,
            preferred_element_type=self.ct, precision=self.prec)

    def written(self, j: int, St):
        """``u'`` of chunk ``j``: what its tokens really write, given
        the state they start from."""
        return self.us[self.L.rows(j)] - self.dot(
            self.w[self.L.rows(j)], St, _NT)

    def leave(self, j: int, St, u):
        """The state chunk ``j`` leaves."""
        return St * self.decay[j] + self.dot(u, self.k_out[j], _TN)


def _group_forward(q, k, v, g, beta, St, scale, C, mm, prec):
    """``R / C`` chunks of one head, one after another, from the state
    transposed, ``St`` ``(d_v, d_k)``.  Returns ``(o (R, d_v), the last
    chunk's last state)``: `_chunked_heads`, a few chunks at a time."""
    m = _Turn(q, k, v, g, beta, scale, C, mm, prec)
    L = m.L
    q_in = q * m.eG * scale
    os = []
    for j in range(L.side):
        u = m.written(j, St)
        # chunk j's pairs against its own u: the others' lanes meet zeros
        among = jnp.concatenate([u if i == j else jnp.zeros_like(u)
                                 for i in range(L.side)], axis=0)
        os.append(m.dot(q_in[L.rows(j)], St, _NT)
                  + m.dot(L.own(m.pairs, j), among, _NN))
        St = m.leave(j, St, u)
    return jnp.concatenate(os, axis=0), St


def _suffix_sums(x, period: int):
    """Sums up the rows of ``x`` ``(n, d)`` from each row to the end of
    its ``period`` rows: `_cumsum_rows` the other way."""
    from jax.experimental.pallas import tpu as pltpu

    n = x.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0) % period
    shift = 1
    while shift < period:
        x = x + jnp.where(row < period - shift,
                          pltpu.roll(x, n - shift, 0), 0)
        shift *= 2
    return x


def _group_backward(q, k, v, g, beta, do, St, dSt, inv, scale, C, mm, prec):
    """The adjoint of `_group_forward`: from the state the turn starts
    from, ``St``, its chunks' inverses ``inv`` ``(C, R)``, the output's
    adjoint ``do`` ``(R, d_v)`` and the adjoint ``dSt`` of the state the
    turn leaves, to ``(dq, dk, dv, dg, dbeta (1, R), the adjoint of the
    state the turn starts from)``.

    Products with the state, its adjoint or the pairs, and the Grams'
    own, take their operands in the inputs' type, as the plain path's
    transposed products do; what stands in for the triangular solve's
    adjoint (``T^T [dw | du]`` and ``dA``) is float32 at ``HIGHEST``."""
    m = _Turn(q, k, v, g, beta, scale, C, mm, prec, inv=inv)
    L, G, eG = m.L, m.G, m.eG
    side, R = L.side, L.R
    dk_ = k.shape[1]
    q_in, ke = q * eG * scale, k * eG
    col_sum = lambda x: jnp.sum(x, axis=0, keepdims=True)
    row_sum = lambda x: jnp.sum(x, axis=1, keepdims=True)
    whole = lambda xs: jnp.concatenate(xs, axis=0)
    # Forward through the turn: the state each chunk starts from and
    # what it writes.
    entry, wrote = [], []
    for j in range(side):
        entry.append(St)
        wrote.append(m.written(j, St))
        if j + 1 < side:
            St = m.leave(j, St, wrote[j])
    # What does not wait for the state's adjoint: P^T do and dP.
    from_pairs = m.dot(L.diagonal(m.pairs), do, _TN)
    d_pairs = L.blocks(m.dot(do, whole(wrote), _NT))
    # Back through the turn.
    dq_in, dk_out, dw, du, d_end = ([None] * side for _ in range(5))
    for j in range(side - 1, -1, -1):
        own = L.rows(j)
        du[j] = from_pairs[own] + m.dot(m.k_out[j], dSt, _NT)
        both = jnp.concatenate([do[own], -du[j]], axis=0)
        read = m.dot(both, entry[j], _NN)
        dq_in[j], dw[j] = read[:C], read[C:]
        dk_out[j] = m.dot(wrote[j], dSt, _NN)
        d_end[j] = (m.decay[j] * col_sum(entry[j] * dSt)
                    + col_sum(dk_out[j] * m.k_out[j]))
        dSt = dSt * m.decay[j] + m.dot(
            both, jnp.concatenate([q_in[own], m.w[own]], axis=0), _TN)
    dq_in, dk_out, k_out = whole(dq_in), whole(dk_out), whole(m.k_out)
    # The UT transform's adjoint: y = T^T [dw | du] is the adjoint of
    # [beta k exp(G) | beta v], and dA = -strict(y_w w^T + y_u u^T).
    y = m.exact(m.wide, jnp.concatenate([whole(dw), whole(du)], axis=1), _TN)
    y_w, y_u = y[:, :dk_], y[:, dk_:]
    dA = jnp.where(L.row > L.col, -L.blocks(m.exact(
        y, jnp.concatenate([m.w, m.us], axis=1), _NT)), 0)
    d_kk = dA * m.beta
    d_qk = jnp.where(L.row >= L.col, d_pairs, 0) * scale
    # (kk past the diagonal may be anything, infinite too: selected out)
    d_beta = (row_sum(y_w * ke) + row_sum(y_u * v) + whole(
        [row_sum(L.own(jnp.where(L.row > L.col, dA * m.kk, 0), j))
         for j in range(side)]))
    # Through the two Grams, block by block as they were built.
    at_mid = jax.lax.broadcasted_iota(jnp.int32, (_SUB, 1), 0) == _SUB // 2
    dq_rows, dk_rows, dG_rows = ({} for _ in range(3))
    dk_cols = dG_cols = jnp.zeros_like(k)
    for r0, (rows, cols, ups, down, rising, falling) in zip(
            range(0, C, _SUB), m.grams):
        d_blk = whole([L.own(d[r0:r0 + _SUB], j) for j in range(side)
                       for d in (d_kk, d_qk)])
        d_rows = m.dot(d_blk, cols, _NN)
        d_cols = m.dot(d_blk, rows, _TN)
        on_rows, on_cols = d_rows * rows, jnp.where(falling, d_cols * cols, 0)
        dk_cols, dG_cols = dk_cols + d_cols * down, dG_cols - on_cols
        for j in range(side):
            for_k = slice(2 * j * _SUB, (2 * j + 1) * _SUB)
            for_q = slice((2 * j + 1) * _SUB, (2 * j + 2) * _SUB)
            dk_rows[j, r0] = d_rows[for_k] * ups[j]
            dq_rows[j, r0] = d_rows[for_q] * ups[j]
            mine = jnp.where(rising[j], on_rows[for_k] + on_rows[for_q], 0)
            dG_rows[j, r0] = mine + jnp.where(
                at_mid, col_sum(on_cols[L.rows(j)]) - col_sum(mine), 0)
    by_row = lambda d: whole([d[j, r0] for j in range(side)
                              for r0 in range(0, C, _SUB)])
    dq = dq_in * eG * scale + by_row(dq_rows)
    dk = (y_w * beta * eG + dk_out * whole(m.fade) + by_row(dk_rows)
          + dk_cols)
    last = jax.lax.broadcasted_iota(jnp.int32, (C, 1), 0) == C - 1
    dG = (y_w * beta * ke + dq_in * q_in - dk_out * k_out
          + by_row(dG_rows) + dG_cols
          + whole([jnp.where(last, d_end[j], 0) for j in range(side)]))
    # a value a token, along the lanes: down the diagonal, then summed
    eye = (jax.lax.broadcasted_iota(jnp.int32, (R, R), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (R, R), 1))
    return (dq, dk, y_u * beta, _suffix_sums(dG, C),
            col_sum(jnp.where(eye, d_beta, 0)), dSt)


def _turn_of(refs, beta_ref, head, rows, live, ct):
    """A turn's rows of each of ``refs`` in the state's type ``ct`` and
    the head's column of ``beta``; what lies past the sequence's end is
    no token (it writes nothing, does not decay and is not read)."""
    xs = [ref[0, rows, :].astype(ct) for ref in refs]
    every = beta_ref[0, rows, :].astype(ct)                # (group, heads)
    lane = jax.lax.broadcasted_iota(jnp.int32, every.shape, 1)
    xs.append(jnp.sum(jnp.where(lane == head, every, 0), axis=1,
                      keepdims=True))
    return xs if live is None else [jnp.where(live, x, 0) for x in xs]


def _live(seq, tokens, block, c, group):
    """Which of a turn's rows are tokens, or ``None`` where every block
    ends inside the sequence."""
    if seq % tokens == 0:
        return None
    return block * tokens + c * group + jax.lax.broadcasted_iota(
        jnp.int32, (group, 1), 0) < seq


def _forward_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, state, *,
                    seq, scale, chunk, prec):
    """A block of tokens of one head of one sequence, a group of chunks
    at a time; ``state`` carries the head's state (transposed) from
    block to block."""
    from jax.experimental import pallas as pl

    tokens, group = q_ref.shape[1], max(_LANES, chunk)
    head, t = pl.program_id(1), pl.program_id(2)

    @pl.when(t == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    def one_group(c, St):
        rows = pl.ds(pl.multiple_of(c * group, group), group)
        q, k, v, g, beta = _turn_of((q_ref, k_ref, v_ref, g_ref), beta_ref,
                                    head, rows,
                                    _live(seq, tokens, t, c, group),
                                    state.dtype)
        o, St = _group_forward(q, k, v, g, beta, St, scale, chunk,
                               q_ref.dtype, prec)
        o_ref[0, rows, :] = o.astype(o_ref.dtype)
        return St

    state[...] = jax.lax.fori_loop(0, tokens // group, one_group, state[...])


def _states_kernel(k_ref, v_ref, g_ref, beta_ref, states_ref, inv_ref, state,
                   *, seq, chunk, prec):
    """The forward kernel again for the backward's sake: it writes, not
    ``o``, the state each turn starts from and its chunks' inverses."""
    from jax.experimental import pallas as pl

    tokens, group = k_ref.shape[1], max(_LANES, chunk)
    head, t = pl.program_id(1), pl.program_id(2)

    @pl.when(t == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    def one_group(c, St):
        rows = pl.ds(pl.multiple_of(c * group, group), group)
        k, v, g, beta = _turn_of((k_ref, v_ref, g_ref), beta_ref, head, rows,
                                 _live(seq, tokens, t, c, group),
                                 state.dtype)
        states_ref[0, 0, c] = St
        m = _Turn(None, k, v, g, beta, None, chunk, k_ref.dtype, prec)
        inv_ref[0, 0, c] = m.inv
        for j in range(m.L.side):
            St = m.leave(j, St, m.written(j, St))
        return St

    state[...] = jax.lax.fori_loop(0, tokens // group, one_group, state[...])


def _backward_kernel(q_ref, k_ref, v_ref, g_ref, do_ref, beta_ref, states_ref,
                     inv_ref, dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref,
                     dstate, *, seq, scale, chunk, prec):
    """The reverse walk: the grid's last axis takes the token blocks
    last to first, the loop a block's turns last to first; ``dstate``
    carries the adjoint of the head's state (transposed) from block to
    block, zero past the sequence's end."""
    from jax.experimental import pallas as pl

    tokens, group = q_ref.shape[1], max(_LANES, chunk)
    head, t = pl.program_id(1), pl.program_id(2)
    block, turns = pl.num_programs(2) - 1 - t, tokens // group

    @pl.when(t == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)

    def one_group(i, dSt):
        c = turns - 1 - i
        rows = pl.ds(pl.multiple_of(c * group, group), group)
        q, k, v, g, do, beta = _turn_of(
            (q_ref, k_ref, v_ref, g_ref, do_ref), beta_ref, head, rows,
            _live(seq, tokens, block, c, group), dstate.dtype)
        dq, dk, dv, dg, dbeta, dSt = _group_backward(
            q, k, v, g, beta, do, states_ref[0, 0, c], dSt, inv_ref[0, 0, c],
            scale, chunk, q_ref.dtype, prec)
        for ref, dx in ((dq_ref, dq), (dk_ref, dk), (dv_ref, dv),
                        (dg_ref, dg)):
            ref[0, rows, :] = dx.astype(ref.dtype)
        dbeta_ref[0, 0, :, rows] = dbeta
        return dSt

    dstate[...] = jax.lax.fori_loop(0, turns, one_group, dstate[...])


def _fit(s: int, a_token: float, chunk: int, block: int):
    """Tokens a grid step: ``block`` at most, whole turns, halved until
    two blocks of ``a_token`` bytes a token fit `_BLOCK_BYTES`."""
    group = max(_LANES, chunk)
    tokens = min(block, -(-s // group) * group)
    while 2 * tokens * a_token > _BLOCK_BYTES and tokens % (2 * group) == 0:
        tokens //= 2
    return tokens, int(2 * tokens * a_token)


def forward_block(s: int, heads: int, dk: int, dv: int, dtype,
                  chunk: int = CHUNK, block: int = _BLOCK_TOKENS):
    """``(tokens, bytes)``: how many tokens a grid step of the kernel
    takes of a sequence of ``s``, and what its blocks take of fast
    memory: ``q``, ``k``, ``v`` in ``dtype``, the decay and the output
    in float32, ``beta`` for all heads (padded to whole lanes), each
    twice (the next block arrives while this one is worked on), and the
    state.  Readable without a chip; held against Mosaic by
    ``tests/test_v5e_compile.py``."""
    size = jnp.dtype(dtype).itemsize
    a_token = (2 * dk + dv) * size + (dk + dv + -(-heads // 128) * 128) * 4
    tokens, staged = _fit(s, a_token, chunk, block)
    return tokens, staged + dk * dv * 4


def backward_block(s: int, heads: int, dk: int, dv: int, dtype,
                   chunk: int = CHUNK, block: int = _BLOCK_TOKENS):
    """``{kernel's name: (tokens, bytes)}`` for the two backward kernels,
    as :func:`forward_block` counts.  The backward's forward stages
    ``k``, ``v``, the decay and ``beta`` and writes a state and its
    chunks' inverses a turn; the reverse walk stages all five inputs,
    the output's adjoint, the states and the inverses, and writes the
    five gradients (``beta``'s a row of 8 sublanes)."""
    size = jnp.dtype(dtype).itemsize
    group = max(_LANES, chunk)
    beta = -(-heads // 128) * 128 * 4
    kept = (dk * dv + chunk * group) * 4 / group     # a turn's, by token
    out = {}
    for name, a_token in zip(BACKWARD_KERNEL_NAMES, (
            (dk + dv) * size + dk * 4 + beta + kept,
            2 * (2 * dk + dv) * size + (2 * dk + dv) * 4 + beta + kept
            + 8 * 4)):
        tokens, staged = _fit(s, a_token, chunk, block)
        out[name] = tokens, staged + dk * dv * 4
    return out


def _pallas_forward(q, k, v, g, beta, scale, chunk, interpret: bool,
                    block: int = _BLOCK_TOKENS):
    """``o`` ``(b, s, h, d_v)`` in the state's type, as the plain path
    returns it.  The grid is ``(batch, head, token block)``, the token
    blocks in order; every operand is read where it lies, ``(b, s, h *
    d)`` with the head as the block's index along the last axis,
    ``beta`` ``(b, s, h)`` all heads a block (the kernel picks its
    head's column); the state never leaves fast memory."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, s, h, dk = q.shape
    dv = v.shape[-1]
    ct = _state_dtype(q)
    tokens, _ = forward_block(s, h, dk, dv, q.dtype, chunk, block)
    flat = lambda x: x.reshape(b, s, -1)
    per_head = lambda d: pl.BlockSpec((1, tokens, d), lambda i, j, t: (i, t, j),
                                      memory_space=pltpu.VMEM)
    o = pl.pallas_call(
        functools.partial(_forward_kernel, seq=s, scale=scale, chunk=chunk,
                          prec=dot_precision(q.dtype)),
        out_shape=jax.ShapeDtypeStruct((b, s, h * dv), ct),
        grid=(b, h, -(-s // tokens)),
        in_specs=[per_head(dk), per_head(dk), per_head(dv), per_head(dk),
                  pl.BlockSpec((1, tokens, h), lambda i, j, t: (i, t, 0),
                               memory_space=pltpu.VMEM)],
        out_specs=per_head(dv),
        scratch_shapes=[pltpu.VMEM((dv, dk), ct)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=KERNEL_NAME,
    )(flat(q), flat(k), flat(v), flat(g), beta)
    return o.reshape(b, s, h, dv)


def _pallas_backward(q, k, v, g, beta, do, scale, chunk, interpret: bool,
                     block: int = _BLOCK_TOKENS):
    """The five gradients, each in its input's shape and type, by two
    kernels on the forward's grid.  The first walks the sequence as the
    forward does and keeps, a turn, the state it starts from and its
    chunks' inverses (float32, freed when the second has read them); the
    second takes the token blocks last to first (by its index maps: no
    operand is flipped) with the state's adjoint in scratch, and writes
    the gradients where the inputs lie, ``(b, s, h * d)``, ``beta``'s a
    head a row, ``(b, h, 1, s)``, turned here."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, s, h, dk = q.shape
    dv = v.shape[-1]
    ct = _state_dtype(q)
    group = max(_LANES, chunk)
    (ahead, _), (back, _) = backward_block(s, h, dk, dv, q.dtype, chunk,
                                           block).values()
    turns = -(-s // ahead) * (ahead // group)    # whole blocks of both
    flat = lambda x: x.reshape(b, s, -1)
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))
    prec = dot_precision(q.dtype)

    def specs(tokens, block_of):
        at = lambda i, j, t: (i, block_of(t), j)
        per = tokens // group
        return dict(
            per_head=lambda d: pl.BlockSpec((1, tokens, d), at,
                                            memory_space=pltpu.VMEM),
            beta=pl.BlockSpec((1, tokens, h),
                              lambda i, j, t: (i, block_of(t), 0),
                              memory_space=pltpu.VMEM),
            states=pl.BlockSpec((1, 1, per, dv, dk),
                                lambda i, j, t: (i, j, block_of(t), 0, 0),
                                memory_space=pltpu.VMEM),
            inv=pl.BlockSpec((1, 1, per, chunk, group),
                             lambda i, j, t: (i, j, block_of(t), 0, 0),
                             memory_space=pltpu.VMEM))

    sp = specs(ahead, lambda t: t)
    states, inv = pl.pallas_call(
        functools.partial(_states_kernel, seq=s, chunk=chunk, prec=prec),
        out_shape=(jax.ShapeDtypeStruct((b, h, turns, dv, dk), ct),
                   jax.ShapeDtypeStruct((b, h, turns, chunk, group), ct)),
        grid=(b, h, -(-s // ahead)),
        in_specs=[sp["per_head"](dk), sp["per_head"](dv),
                  sp["per_head"](dk), sp["beta"]],
        out_specs=(sp["states"], sp["inv"]),
        scratch_shapes=[pltpu.VMEM((dv, dk), ct)],
        compiler_params=params, interpret=interpret,
        name=BACKWARD_KERNEL_NAMES[0],
    )(flat(k), flat(v), flat(g), beta)

    n = -(-s // back)
    sp = specs(back, lambda t: n - 1 - t)
    like = lambda x: jax.ShapeDtypeStruct((b, s, x.shape[2] * x.shape[3]),
                                          x.dtype)
    dq, dk_, dv_, dg, dbeta = pl.pallas_call(
        functools.partial(_backward_kernel, seq=s, scale=scale, chunk=chunk,
                          prec=prec),
        out_shape=(like(q), like(k), like(v), like(g),
                   jax.ShapeDtypeStruct((b, h, 1, n * back), ct)),
        grid=(b, h, n),
        in_specs=[sp["per_head"](dk), sp["per_head"](dk), sp["per_head"](dv),
                  sp["per_head"](dk), sp["per_head"](dv), sp["beta"],
                  sp["states"], sp["inv"]],
        out_specs=(sp["per_head"](dk), sp["per_head"](dk),
                   sp["per_head"](dv), sp["per_head"](dk),
                   pl.BlockSpec((1, 1, 1, back),
                                lambda i, j, t: (i, j, 0, n - 1 - t),
                                memory_space=pltpu.VMEM)),
        scratch_shapes=[pltpu.VMEM((dv, dk), ct)],
        compiler_params=params, interpret=interpret,
        name=BACKWARD_KERNEL_NAMES[1],
    )(flat(q), flat(k), flat(v), flat(g), flat(do.astype(ct)), beta, states,
      inv)
    dbeta = jnp.moveaxis(dbeta[:, :, 0, :s], 1, 2)
    return (dq.reshape(q.shape), dk_.reshape(k.shape), dv_.reshape(v.shape),
            dg.reshape(g.shape).astype(g.dtype), dbeta.astype(beta.dtype))
