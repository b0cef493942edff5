"""State-space duality: the Mamba-2 recurrence with a scalar decay a head.

One head keeps a state ``H`` of shape ``(p, n)`` (``p`` channels of the
head, ``n`` the state size) and reads it out after each token::

    a_t = exp(A delta_t)                         A < 0, delta_t > 0
    H_t = a_t H_{t-1} + delta_t x_t (x) B_t
    y_t = H_t C_t + D x_t

``B_t`` and ``C_t`` ``(n,)`` are shared by the heads of a group (head
``h`` reads group ``h // (heads / groups)``).  Three ways behind one
arithmetic:

* :func:`ssd_recurrent` is the definition, one token at a time under
  ``lax.scan``: the oracle, as ``kda.py`` keeps its own;
* :func:`ssd_chunked` is what a prefill runs: chunks of 128 tokens,
  inside a chunk matrix products, across chunks a ``lax.scan`` over the
  state.  It takes the state a sequence starts from and hands back the
  one it ends in, so that a prefill's last state is a decode's first;
* :func:`ssd_step` is what a decode step runs: one token for every
  sequence of a batch, the state read once and written once: on a TPU,
  for eligible shapes, a Pallas kernel (:data:`KERNEL_NAME`) that takes
  a block of states through fast memory once and writes it back where it
  lay; everywhere else plain jnp, the same arithmetic.  (Left to the
  compiler the update and the read-out become two fusions that each
  read the old state: three passes over it where two are needed.)

With ``G_r`` the log-decay summed from the chunk's first token to token
``r`` and ``H_in`` the state the chunk starts from::

    y_r   = sum_{i<=r} exp(G_r - G_i) (C_r . B_i) delta_i x_i
            + exp(G_r) H_in C_r + D x_r
    H_out = exp(G_last) H_in + sum_i exp(G_last - G_i) delta_i x_i (x) B_i

The decay is one number a head and token, so ``exp(G_r - G_i)`` for ``i
<= r`` never exceeds one: no delta rule, no triangular solve, no
sub-blocks (``kda.py`` needs all three for its per-channel decay).  What
the two files share is the skeleton.  The state, the decays and every
sum are at least float32; matrix products take their operands in the
inputs' type (bfloat16 inputs: one MXU pass, float32 accumulation).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .flash import _on_tpu, dot_precision

CHUNK = 128
# The name the decode update's kernel carries into traces and HLO.
KERNEL_NAME = "mpi4torch_ssd_update"
# Bytes of states one grid step takes through fast memory (in, and as
# much out, each double-buffered): four such buffers fit Mosaic's
# default scoped limit with room.
_BLOCK_BYTES = 1 << 20


def _state_dtype(x):
    # At least f32; f64 inputs keep f64 (the x64 suite's oracles).
    return jnp.promote_types(x.dtype, jnp.float32)


def _grouped(t, groups: int):
    """``t`` ``(b, s, heads, ...)`` with its head axis split as
    ``(groups, heads / groups)``."""
    return t.reshape(*t.shape[:2], groups, t.shape[2] // groups,
                     *t.shape[3:])


def _kernel_eligible(h, groups: int) -> bool:
    """Shapes the kernel takes: float32 states whose ``(p, n)`` tiles as
    it lies (``n`` whole lanes, ``p`` whole sublanes), heads in whole
    groups."""
    _, heads, p, n = h.shape
    return (h.dtype == jnp.float32 and n % 128 == 0 and p % 8 == 0
            and heads % groups == 0
            and (heads // groups) * p * n * 4 <= _BLOCK_BYTES)


def uses_kernel(h, groups: int) -> bool:
    """Whether :func:`ssd_step` (``impl="auto"``) runs its kernel."""
    return _kernel_eligible(h, groups) and _on_tpu()


def _update_kernel(h_ref, decay_ref, write_ref, b_ref, c_ref, h_out, y_out):
    """A block of ``sb`` sequences' states of one group: ``h`` ``(sb, k,
    p, n)`` (``k`` heads of the group); ``decay`` and ``write`` ``(sb,
    1, p, k)``, a head's decay and its ``delta x`` down the lanes'
    column ``k``; ``b`` and ``c`` ``(sb, 1, 1, n)``.  Writes the new
    states and ``y`` ``(sb, 1, p, k)``."""
    sb, k = h_ref.shape[0], h_ref.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, y_out.shape[2:], 1)
    for s in range(sb):
        b, c = b_ref[s, 0], c_ref[s, 0]                     # (1, n)
        y = jnp.zeros(y_out.shape[2:], jnp.float32)
        for j in range(k):
            new = decay_ref[s, 0, :, j:j + 1] * h_ref[s, j] \
                + write_ref[s, 0, :, j:j + 1] * b
            h_out[s, j] = new
            y = jnp.where(lane == j,
                          jnp.sum(new * c, axis=1, keepdims=True), y)
        y_out[s, 0] = y


def _pallas_step(h, decay, write, B, C, interpret: bool):
    """``(y (b, heads, p), new h)`` from ``h`` ``(b, heads, p, n)``,
    ``decay`` ``(b, heads)``, ``write = delta x`` ``(b, heads, p)`` and
    ``B``, ``C`` ``(b, groups, n)``, all float32; ``h``'s buffer is the
    new states'."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, heads, p, n = h.shape
    groups = B.shape[1]
    k = heads // groups
    sb = max(1, _BLOCK_BYTES // (k * p * n * 4))
    while b % sb:
        sb -= 1
    # A head's scalars down a column of lanes: (b, groups, p, k).
    column = lambda t: jnp.moveaxis(t.reshape(b, groups, k, p), 2, 3)
    decay = column(jnp.broadcast_to(decay[..., None], (b, heads, p)))
    row = lambda t: t[:, :, None, :]
    block = lambda *shape: pl.BlockSpec(
        (sb,) + shape, lambda i, g: (i, g, 0, 0), memory_space=pltpu.VMEM)
    new, y = pl.pallas_call(
        _update_kernel,
        out_shape=(jax.ShapeDtypeStruct(h.shape, h.dtype),
                   jax.ShapeDtypeStruct((b, groups, p, k), h.dtype)),
        grid=(b // sb, groups),
        in_specs=[block(k, p, n), block(1, p, k), block(1, p, k),
                  block(1, 1, n), block(1, 1, n)],
        out_specs=(block(k, p, n), block(1, p, k)),
        input_output_aliases={0: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name=KERNEL_NAME,
    )(h, decay, column(write), row(B), row(C))
    return jnp.moveaxis(y, 3, 2).reshape(b, heads, p), new


def ssd_step(h, x, dt, A, B, C, D, impl: str = "auto"):
    """One token for every sequence: ``h`` ``(b, heads, p, n)`` the
    states, ``x`` ``(b, heads, p)``, ``dt`` ``(b, heads)`` the step
    sizes (``delta``, positive), ``A`` ``(heads,)`` negative, ``B`` and
    ``C`` ``(b, groups, n)``, ``D`` ``(heads,)``.  Returns ``(y (b,
    heads, p) in x's type, the new states in at least float32)``: every
    state element is read once and written once, and nothing of the
    states' size is made beside them.  ``impl``: ``"auto"`` (the kernel
    on a TPU for eligible shapes, else jnp), ``"pallas"`` (forced;
    interpreted off the TPU, for tests) or ``"jnp"``."""
    if impl not in ("auto", "pallas", "jnp"):
        raise ValueError(f"unknown impl {impl!r}")
    ct = _state_dtype(h)
    h = h.astype(ct)
    heads, groups = x.shape[1], B.shape[1]
    dt = dt.astype(ct)
    xf = x.astype(ct)
    decay = jnp.exp(A.astype(ct) * dt)
    write = xf * dt[..., None]
    if impl == "pallas" or (impl == "auto" and uses_kernel(h, groups)):
        if not _kernel_eligible(h, groups):
            raise ValueError(
                f"the state update's kernel takes float32 states of whole "
                f"(8, 128) tiles in whole groups; got {h.shape} {h.dtype} "
                f"in {groups} groups")
        y, h = _pallas_step(h, decay, write, B.astype(ct), C.astype(ct),
                            interpret=not _on_tpu())
    else:
        per_head = lambda t: jnp.repeat(t.astype(ct), heads // groups,
                                        axis=1)
        h = h * decay[..., None, None] \
            + write[..., None] * per_head(B)[:, :, None, :]
        y = jnp.sum(h * per_head(C)[:, :, None, :], axis=-1)
    return (y + D.astype(ct)[:, None] * xf).astype(x.dtype), h


def ssd_recurrent(x, dt, A, B, C, D, h0=None):
    """The recurrence, token by token.  ``x`` ``(b, s, heads, p)``,
    ``dt`` ``(b, s, heads)``, ``A`` and ``D`` ``(heads,)``, ``B`` and
    ``C`` ``(b, s, groups, n)``, ``h0`` ``(b, heads, p, n)`` (zeros
    where ``None``).  Returns ``(y (b, s, heads, p) in x's type, h_T)``.
    """
    ct = _state_dtype(x)
    b, _, heads, p = x.shape
    if h0 is None:
        h0 = jnp.zeros((b, heads, p, B.shape[-1]), ct)
    seq = lambda t: jnp.moveaxis(t.astype(ct), 1, 0)

    def step(h, inp):
        y, h = ssd_step(h, *inp[:2], A, *inp[2:], D, impl="jnp")
        return h, y

    h, y = jax.lax.scan(step, h0.astype(ct), (seq(x), seq(dt), seq(B),
                                              seq(C)))
    return jnp.moveaxis(y, 0, 1).astype(x.dtype), h


def ssd_chunked(x, dt, A, B, C, D, h0=None, chunk: int = CHUNK):
    """The same function as :func:`ssd_recurrent` in chunked form.  Any
    sequence length: the tail is padded with tokens that write nothing
    and do not decay (``dt = 0``), so the state handed back is the last
    real token's."""
    ct = _state_dtype(x)
    mm = x.dtype
    prec = dot_precision(mm)
    b, s, heads, p = x.shape
    groups, n = B.shape[2], B.shape[3]
    z = -(-s // chunk)

    def chunks(t):    # (b, s, ...) -> (b, z, chunk, ...)
        t = jnp.pad(t, [(0, 0), (0, z * chunk - s)] + [(0, 0)] * (t.ndim - 2))
        return t.reshape(b, z, chunk, *t.shape[2:])

    def dot(eq, u, v):
        return jnp.einsum(eq, u.astype(mm), v.astype(mm),
                          preferred_element_type=ct, precision=prec)

    # Heads as (groups, heads of a group): B and C broadcast over the
    # second.  r, i: rows and columns of a chunk; g, k: group, head.
    dt_c = chunks(_grouped(dt.astype(ct), groups))        # (b z r g k)
    x_c = chunks(_grouped(x, groups))                     # (b z r g k p)
    B_c, C_c = chunks(B), chunks(C)                       # (b z r g n)
    G = jnp.cumsum(dt_c * A.astype(ct).reshape(groups, -1), axis=2)
    xdt = x_c.astype(ct) * dt_c[..., None]
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    span = jnp.moveaxis(G, 2, -1)                         # (b z g k r)
    within = jnp.exp(jnp.where(
        lower, span[..., :, None] - span[..., None, :], -jnp.inf))
    weights = dot("bzrgn,bzign->bzgri", C_c, B_c)[:, :, :, None] * within
    y = dot("bzgkri,bzigkp->bzrgkp", weights, xdt)
    g_end = G[:, :, -1]                                   # (b z g k)
    wrote = dot("bzigkp,bzign->bzgkpn",
                xdt * jnp.exp(g_end[:, :, None] - G)[..., None], B_c)

    def carry(h, inp):
        decay, new = inp
        return h * decay[..., None, None] + new, h

    if h0 is None:
        h0 = jnp.zeros((b, heads, p, n), ct)
    h_T, h_in = jax.lax.scan(
        carry, h0.astype(ct).reshape(b, groups, heads // groups, p, n),
        (jnp.moveaxis(jnp.exp(g_end), 1, 0), jnp.moveaxis(wrote, 1, 0)))
    y = y + dot("bzrgn,bzgkpn->bzrgkp", C_c, jnp.moveaxis(h_in, 0, 1)) \
        * jnp.exp(G)[..., None]
    y = y.reshape(b, z * chunk, heads, p)[:, :s] \
        + D.astype(ct)[:, None] * x.astype(ct)
    return y.astype(x.dtype), h_T.reshape(b, heads, p, n)
