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
  keeps the five inputs and nothing else, and is the plain path's.
* Everywhere else (float64, the CPU, narrower heads), and under
  ``impl="jnp"``: plain JAX with a ``lax.scan`` over the state,
  differentiated by autodiff through the chunked form: the backward is
  the transposed chunk products and a reverse scan, at the price of the
  chunk-level residuals autodiff keeps.  Those are some thirty float32
  arrays of the size of ``g`` (9 GB for 32 heads of 128 over 16,384
  tokens), so heads are taken ``HEAD_GROUP`` at a time, each group
  rematerialised on the way back: one more forward of the chunk
  products for a quarter of the memory.  The kernel's backward is this
  walk over the head groups, so both paths' gradients are the same
  arithmetic.

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
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .flash import _on_tpu, dot_precision

CHUNK = 64
HEAD_GROUP = 8
_SUB = 16
_EXP_CAP = 80.0
# The name the forward kernel carries into traces and HLO.
KERNEL_NAME = "mpi4torch_kda_fwd"
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
    """Whether :func:`kda_chunked` (``impl="auto"``) runs its forward
    kernel."""
    return _kernel_eligible(q, k, v, chunk) and _on_tpu()


def kda_chunked(q, k, v, g, beta, scale=None, chunk: int = CHUNK,
                impl: str = "auto"):
    """The same function as :func:`kda_recurrent` in chunked form.  Any
    sequence length: the tail is padded with tokens that write nothing
    (``beta = 0``) and do not decay (``g = 0``).  ``impl``: ``"auto"``
    (the forward kernel on a TPU for eligible shapes, else plain JAX),
    ``"pallas"`` (forced; interpreted off the TPU, for tests) or
    ``"jnp"``.  Every gradient is the plain path's either way."""
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
    """The plain path's backward: ``_chunked_heads`` run forward again
    in plain JAX and transposed, a head group at a time."""
    h = inputs[0].shape[2]
    core = functools.partial(_chunked_heads, scale=scale, chunk=chunk)
    if h <= HEAD_GROUP or h % HEAD_GROUP:
        return jax.vjp(core, *inputs)[1](do)
    grads = jax.lax.map(lambda a: jax.vjp(core, *a[:-1])[1](a[-1]),
                        tuple(_head_groups(x) for x in (*inputs, do)))
    return tuple(jnp.moveaxis(dx, 0, 2).reshape(x.shape)
                 for dx, x in zip(grads, inputs))


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


def _group_forward(q, k, v, g, beta, St, scale, C, mm, prec):
    """``R / C`` chunks of one head, one after another: ``q``, ``k``,
    ``g`` ``(R, d_k)``, ``v`` ``(R, d_v)``, ``beta`` ``(R, 1)`` and the
    state transposed, ``St`` ``(d_v, d_k)``, all in the state's type.
    Returns ``(o (R, d_v), the last chunk's last state)``:
    `_chunked_heads`, a few chunks at a time.

    What does not wait for the state (the decay, the Grams, the inverse
    of ``I + A``, ``w`` and ``u``) is worked out for the chunks
    together, their ``(C, C)`` matrices side by side along the lanes,
    ``(C, R)``: an elementwise step or a product then costs what it
    costs for one chunk (a ``(64, 64)`` float32 array fills half of
    each register it takes, and a product's cost goes by the rows
    pushed through the MXU).  ``X Y`` chunk by chunk is ``X`` times
    ``Y``'s blocks laid down the diagonal of ``(R, R)``."""
    ct = St.dtype
    R = q.shape[0]
    side = R // C
    row = jax.lax.broadcasted_iota(jnp.int32, (C, R), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (C, R), 1)
    col = lane % C

    def by_lane(xs):    # chunk j's (n, R) array on chunk j's lanes
        # (an iota of its own a piece: Mosaic's layout pass aborts on a
        # slice of ``lane``)
        out = xs[-1]
        for j in range(side - 2, -1, -1):
            out = jnp.where(jax.lax.broadcasted_iota(
                jnp.int32, out.shape, 1) < (j + 1) * C, xs[j], out)
        return out

    def diagonal(y):    # (C, R) side by side -> (R, R) down the diagonal
        return jnp.concatenate([jnp.where(lane // C == j, y, 0)
                                for j in range(side)], axis=0)

    def dot(x, y, dims):
        return jax.lax.dot_general(x.astype(mm), y.astype(mm), dims,
                                   preferred_element_type=ct, precision=prec)

    exact = functools.partial(jnp.dot, preferred_element_type=ct,
                              precision=jax.lax.Precision.HIGHEST)
    G = _cumsum_rows(g, C)
    # The decay Grams a block of _SUB rows of every chunk at a time,
    # both sides measured from the block's middle row.  Columns past the
    # block's last row, and other chunks' columns, are computed too
    # (capped, so finite or at worst discarded) and fall to the masks.
    kk, qk = [], []
    for r0 in range(0, C, _SUB):
        mid = r0 + _SUB // 2
        refs = [G[j * C + mid:j * C + mid + 1] for j in range(side)]
        down = jnp.exp(jnp.minimum(jnp.concatenate(
            [jnp.broadcast_to(ref, (C, ref.shape[1])) for ref in refs],
            axis=0) - G, _EXP_CAP))
        rows = []
        for j, ref in enumerate(refs):
            own = slice(j * C + r0, j * C + r0 + _SUB)
            up = jnp.exp(jnp.minimum(G[own] - ref, _EXP_CAP))
            rows += [k[own] * up, q[own] * up]
        blk = dot(jnp.concatenate(rows, axis=0), k * down, _NT)
        pieces = [blk[i * _SUB:(i + 1) * _SUB] for i in range(2 * side)]
        kk.append(by_lane(pieces[0::2]))
        qk.append(by_lane(pieces[1::2]))
    kk, qk = jnp.concatenate(kk, axis=0), jnp.concatenate(qk, axis=0)
    A = jnp.where(row > col, by_lane([jnp.broadcast_to(
        beta[j * C:(j + 1) * C], (C, R)) for j in range(side)]) * kk, 0)
    pairs = jnp.where(row >= col, qk, 0) * scale
    # (I + A)^-1 applied to beta k exp(G) and to beta v: the UT transform.
    inv = diagonal(_unit_lower_inverse(
        A, row, col, lambda x, y: exact(x, diagonal(y))))
    w, us = exact(inv, beta * k * jnp.exp(G)), exact(inv, beta * v)
    q_in = q * jnp.exp(G) * scale
    os = []
    for j in range(side):
        own = slice(j * C, (j + 1) * C)
        g_end = G[(j + 1) * C - 1:(j + 1) * C]
        u = us[own] - dot(w[own], St, _NT)
        # chunk j's pairs against its own u: the others' lanes meet zeros
        among = jnp.concatenate([u if i == j else jnp.zeros_like(u)
                                 for i in range(side)], axis=0)
        os.append(dot(q_in[own], St, _NT) + dot(
            jnp.where(lane // C == j, pairs, 0), among,
            (((1,), (0,)), ((), ()))))
        St = St * jnp.exp(g_end) + dot(
            u, k[own] * jnp.exp(g_end - G[own]), _TN)
    return jnp.concatenate(os, axis=0), St


def _forward_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, state, *,
                    seq, scale, chunk, prec):
    """A block of tokens of one head of one sequence, a group of chunks
    at a time; ``state`` carries the head's state (transposed) from
    block to block."""
    from jax.experimental import pallas as pl

    ct, mm = state.dtype, q_ref.dtype
    tokens, group = q_ref.shape[1], max(_LANES, chunk)
    head, t = pl.program_id(1), pl.program_id(2)

    @pl.when(t == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    def one_group(c, St):
        rows = pl.ds(pl.multiple_of(c * group, group), group)
        q, k, v, g = (ref[0, rows, :].astype(ct)
                      for ref in (q_ref, k_ref, v_ref, g_ref))
        every = beta_ref[0, rows, :].astype(ct)            # (group, heads)
        lane = jax.lax.broadcasted_iota(jnp.int32, every.shape, 1)
        beta = jnp.sum(jnp.where(lane == head, every, 0), axis=1,
                       keepdims=True)
        if seq % tokens:
            # The last block ends past the sequence: what lies there is
            # no token (it writes nothing and does not decay).
            live = t * tokens + c * group + jax.lax.broadcasted_iota(
                jnp.int32, (group, 1), 0) < seq
            q, k, v, g, beta = (jnp.where(live, x, 0)
                                for x in (q, k, v, g, beta))
        o, St = _group_forward(q, k, v, g, beta, St, scale, chunk, mm, prec)
        o_ref[0, rows, :] = o.astype(o_ref.dtype)
        return St

    state[...] = jax.lax.fori_loop(0, tokens // group, one_group, state[...])


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
    group = max(_LANES, chunk)
    tokens = min(block, -(-s // group) * group)
    while 2 * tokens * a_token > _BLOCK_BYTES and tokens % (2 * group) == 0:
        tokens //= 2
    return tokens, 2 * tokens * a_token + dk * dv * 4


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
