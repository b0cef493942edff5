"""Reduction-op constants and combine rules.

Mirrors the reference's library-stable op-code enum ``Mpi4torchCollectiveOps``
(reference: csrc/extension.cpp:204-252) and its torch→MPI dtype mapping
(csrc/extension.cpp:106-129).  The reference supports only
Byte/Char/Short/Int/Long/Float/Double; this framework is a superset: every
dtype JAX supports (including bfloat16/float16, bool, complex) is accepted,
because on TPU bfloat16 is the native matmul/collective dtype.

Op-code values are identical to the reference enum so that serialized
descriptors are interchangeable.
"""

from __future__ import annotations

import jax.numpy as jnp

# Library-stable integer codes (reference: csrc/extension.cpp:204-217).
MPI_MAX = 1
MPI_MIN = 2
MPI_SUM = 3
MPI_PROD = 4
MPI_LAND = 5
MPI_BAND = 6
MPI_LOR = 7
MPI_BOR = 8
MPI_LXOR = 9
MPI_BXOR = 10
MPI_MINLOC = 11
MPI_MAXLOC = 12

_OP_NAMES = {
    MPI_MAX: "MPI_MAX",
    MPI_MIN: "MPI_MIN",
    MPI_SUM: "MPI_SUM",
    MPI_PROD: "MPI_PROD",
    MPI_LAND: "MPI_LAND",
    MPI_BAND: "MPI_BAND",
    MPI_LOR: "MPI_LOR",
    MPI_BOR: "MPI_BOR",
    MPI_LXOR: "MPI_LXOR",
    MPI_BXOR: "MPI_BXOR",
    MPI_MINLOC: "MPI_MINLOC",
    MPI_MAXLOC: "MPI_MAXLOC",
}


def op_name(op: int) -> str:
    return _OP_NAMES.get(op, f"<unknown op {op}>")


def fold_supported(op: int) -> bool:
    """True iff combine2/reduce_ordered can evaluate ``op`` (everything
    but the pair-semantics MINLOC/MAXLOC and unknown codes).  Lets
    callers that delegate a fold to one rank (eager Allreduce fold-once)
    keep unsupported ops on the every-rank path, so the informative
    rejection raises identically on every rank instead of as a rank-0
    death plus broken-barrier aborts elsewhere."""
    return op in _OP_NAMES and op not in (MPI_MINLOC, MPI_MAXLOC)


_BITWISE_OPS = (MPI_BAND, MPI_BOR, MPI_BXOR)


def fold_applicable(op: int, dtype) -> bool:
    """Dtype-aware :func:`fold_supported`: True iff combine2 can evaluate
    ``op`` on operands of ``dtype`` without raising.

    The fold-delegation gates (eager Allreduce fold-once, Reduce_'s
    root-only fold) must key on this, not on :func:`fold_supported`
    alone: an op that is supported in general but invalid for the dtype
    (e.g. ``MPI_BAND`` on floats — bitwise ops are integer/bool-only,
    like MPI's own op/dtype table, reference csrc/extension.cpp:106-129)
    would otherwise raise only on the folding rank while the other ranks
    skip ahead — a rank death plus broken-barrier aborts instead of the
    symmetric informative error on every rank (ADVICE r5)."""
    if not fold_supported(op):
        return False
    import numpy as _np

    if op in _BITWISE_OPS:
        return _np.dtype(dtype).kind in "iub"
    return True


def combine2(op: int, a, b):
    """Elementwise combination of two operands for reduction op ``op``.

    Used by the eager (thread-SPMD) backend to reduce deterministically in
    ascending rank order — the analogue of MPI's commutative-op reduction but
    with a *fixed* evaluation order, which is what makes gradients bit-exact
    and run-to-run reproducible (BASELINE.md north-star requirement).

    MPI_MINLOC/MPI_MAXLOC operate on (value, index) pairs in MPI; the
    reference forwards them to MPI with a scalar datatype, which MPI rejects
    at runtime (csrc/extension.cpp:106-129 has no pair types).  We reject
    them here with a clear error instead.

    Plain-numpy operands combine in numpy so their dtype is preserved
    exactly (jnp would canonicalize f64->f32 with x64 off), keeping the
    fallback fold bit-equal to the native kernel for every op.
    """
    import numpy as _np
    xp = _np if (isinstance(a, _np.ndarray) and isinstance(b, _np.ndarray)) \
        else jnp
    if op == MPI_SUM:
        return a + b
    if op == MPI_MAX:
        return xp.maximum(a, b)
    if op == MPI_MIN:
        return xp.minimum(a, b)
    if op == MPI_PROD:
        return a * b
    if op == MPI_LAND:
        return xp.logical_and(a != 0, b != 0).astype(a.dtype)
    if op == MPI_BAND:
        return a & b
    if op == MPI_LOR:
        return xp.logical_or(a != 0, b != 0).astype(a.dtype)
    if op == MPI_BOR:
        return a | b
    if op == MPI_LXOR:
        return xp.logical_xor(a != 0, b != 0).astype(a.dtype)
    if op == MPI_BXOR:
        return a ^ b
    if op in (MPI_MINLOC, MPI_MAXLOC):
        raise NotImplementedError(
            f"{op_name(op)} requires (value, index) pair semantics; the MPI "
            "reference forwards plain tensors to MPI which rejects them at "
            "runtime (no pair datatype in csrc/extension.cpp:106-129). "
            "Use Allreduce(MPI_MIN/MPI_MAX) plus an argmin/argmax instead."
        )
    raise ValueError(f"Unknown reduction op code {op}")


def reduce_rhd(op, values):
    """Reduce per-rank tensors in the recursive-halving/doubling
    association: a balanced binary tree pairing rank ``i`` with rank
    ``i + h`` at halving distance ``h = n/2, n/4, ..., 1``.

    This is exactly the association the SPMD ``rhd`` schedule
    (ops/spmd.py ``_rhd_allreduce_value``) produces on the wire, so the
    eager rendezvous backend folding with this helper is bit-identical
    to the compiled butterfly — the Mode A / Mode B parity contract per
    algorithm (all MPI fold ops are commutative, so only the
    association — which this fixes — affects bits).  Requires a
    power-of-two count, like the schedule itself."""
    vals = list(values)
    n = len(vals)
    if n & (n - 1):
        raise ValueError(
            f"reduce_rhd needs a power-of-two rank count, got {n}")
    while n > 1:
        h = n // 2
        vals = [combine2(op, vals[i], vals[i + h]) for i in range(h)]
        n = h
    return vals[0]


def reduce_tree(op, values):
    """Reduce per-rank tensors in the binomial-tree-toward-rank-0
    association: at step ``s = 2^(k-1), ..., 2, 1`` every rank
    ``r < s`` with ``r + s < n`` absorbs rank ``r + s``'s partial.

    Matches the SPMD ``tree`` schedule (ops/spmd.py
    ``_tree_reduce_value`` with root relabeled to position 0), so eager
    rendezvous results are bit-identical to the compiled tree — and,
    unlike :func:`reduce_rhd`, it is defined for any rank count."""
    vals = list(values)
    n = len(vals)
    step = 1
    while step < n:
        step *= 2
    step //= 2
    while step >= 1:
        for r in range(step):
            if r + step < n:
                vals[r] = combine2(op, vals[r], vals[r + step])
        step //= 2
    return vals[0] if vals else None


def reduce_grouped(op, values, group: int):
    """Reduce per-rank tensors in the hierarchical 2-level association:
    ascending fold within each block of ``group`` consecutive ranks,
    then ascending fold of the per-group partials.

    Matches the deterministic form of the SPMD ``hier`` schedule
    (ops/spmd.py ``_hier_allreduce_value``), where groups are
    consecutive runs along the axis (the intra-tier of a 2-level
    topology).  Since ISSUE 14 the fold body is the schedule-IR
    interpreter's one ``level_fold`` path (csched.interp) — the same
    code that executes the hier program for the eager rendezvous
    backend — so this helper, :func:`reduce_torus`, and the eager
    hier/torus legs can never drift apart."""
    vals = list(values)
    n = len(vals)
    if group < 1 or n % group:
        raise ValueError(
            f"reduce_grouped needs group ({group}) to divide the rank "
            f"count ({n})")
    from .csched.interp import level_fold_groups
    from .csched.programs import _hier_groups

    inner, outer, _ = _hier_groups(n, group)
    return level_fold_groups(
        outer, op, level_fold_groups(inner, op, vals))[0]


def multipath_split(total: int) -> int:
    """THE split point of a multipath payload: the first ``multipath_split``
    flat elements ride channel 0, the rest channel 1.  One shared rule for
    the SPMD ``bidir``/``torus`` schedules (ops/spmd.py) and the eager
    folds below, so Mode A and Mode B can never disagree about which
    element belongs to which channel."""
    return -(-int(total) // 2)


def reduce_torus(op, values, inner: int):
    """Reduce per-rank tensors in the 2-axis torus multipath association
    (the SPMD ``torus`` schedule, ops/spmd.py): ranks form a row-major
    ``(outer, inner)`` grid, the flat payload splits at
    :func:`multipath_split`, and each half folds in the 2-level grouped
    association of its own channel —

    * **half 0** (inner-axis channel): ascending fold within each block
      of ``inner`` consecutive ranks, then ascending over the block
      partials (exactly :func:`reduce_grouped`);
    * **half 1** (outer-axis channel): ascending fold within each
      outer-axis group ``{i, i+inner, i+2·inner, …}``, then ascending
      over the per-column partials — the same grouped fold on the
      transposed grid.

    Bit-identical to the deterministic form of the compiled schedule on
    both the flat-axis (``axis_index_groups``) and the two-axis
    (``comm_from_mesh(mesh, (outer, inner))``) communicator."""
    vals = list(values)
    n = len(vals)
    if inner < 1 or n % inner:
        raise ValueError(
            f"reduce_torus needs inner ({inner}) to divide the rank "
            f"count ({n})")
    if n == 1:
        return vals[0]
    # The fold IS the torus program's interpretation (ISSUE 14 dedupe):
    # the deterministic torus channels — half 0 grouped (inner-axis
    # first), half 1 the transposed grid — executed by the schedule-IR
    # interpreter's one level_fold path, the same code the eager
    # rendezvous backend folds with for algorithm="torus".
    from .csched.interp import interpret_allreduce
    from .csched.ir import Phase, Program, Step
    from .csched.programs import _hier_groups

    inner_groups, outer_groups, outer_n = _hier_groups(n, inner)
    ch0 = (Step("level_fold", (inner_groups, inner), span=("half", 0)),
           Step("level_fold", (outer_groups, outer_n), span=("half", 0)))
    ch1 = (Step("level_fold", (outer_groups, outer_n), span=("half", 1)),
           Step("level_fold", (inner_groups, inner), span=("half", 1)))
    prog = Program("allreduce", "torus", n,
                   (Phase("multipath", ch0 + ch1),))
    return interpret_allreduce(prog, op, vals)


def multipath_ring_orders(n: int, algorithm, *, inner=None,
                          reverse: bool = False):
    """THE channel schedules of the quantized multipath collectives: a
    tuple of ``(sigma, direction)`` ring channels, one per multipath
    channel of ``algorithm``.  ``sigma`` maps ring *position* to rank
    (``None`` = identity: position ``p`` is rank ``p``); ``direction``
    is the ring step (+1/-1).  The flat payload splits at
    :func:`multipath_split` across the channels, and each channel runs
    the in-schedule quantized ring (compress/spmd.py) on its half.

    * ``ring`` — one identity channel.
    * ``bidir`` — two counter-rotating identity channels (each rides one
      direction of the bidirectional link); ``reverse`` swaps the
      directions, which is how the backward pass reuses the forward
      machinery (the adjoint of a ring segment is the reverse ring).
    * ``torus`` — two same-direction channels on TRANSPOSED walks of the
      ``(outer, inner)`` rank grid: channel 0 walks ranks row-major
      (inner-axis links), channel 1 column-major (outer-axis links), so
      the halves stripe across the two torus axes.

    One shared rule for the SPMD lowering and the eager fold oracle
    (:func:`reduce_q8_hop`), so Mode A and Mode B can never disagree
    about which rank touches which chunk at which hop."""
    if algorithm in (None, "ring"):
        return ((None, 1),)
    if algorithm == "bidir":
        return ((None, -1), (None, 1)) if reverse else ((None, 1),
                                                        (None, -1))
    if algorithm == "torus":
        if inner is None or inner < 1 or n % inner:
            raise ValueError(
                f"the torus multipath schedule needs an inner group size "
                f"dividing the rank count; got inner={inner} for {n} "
                "ranks")
        outer = n // inner
        sigma = tuple((p % outer) * inner + p // outer for p in range(n))
        return ((None, 1), (sigma, 1))
    raise ValueError(
        f"no multipath ring decomposition for algorithm {algorithm!r} "
        "(the quantized in-schedule pipeline serves ring-shaped "
        "schedules: ring, bidir, torus)")


def _sim_quant_ring(flats, block, sigma, d, salt, stochastic, hop_ef,
                    track):
    """Simulate ONE in-schedule quantized ring channel over the full
    per-rank contribution list — the hop-for-hop, bit-for-bit replica of
    ``compress/spmd.py`` ``_fused_channel`` (same chunk layout, same
    requant op sequence via ops/quant_kernels, same schedule-keyed
    noise).  The hop arithmetic runs through the JITTED forms of the
    fallback ops (quant_kernels._hop_jnp_jit & co) so it compiles
    exactly like the traced pipeline — op-by-op eager execution would
    round the fused multiply-adds differently by 1-2 ulp and break the
    bitwise contract.  Returns ``(reduced_flat,
    per_rank_residual_flats|None)``."""
    from .ops import quant_kernels as qk

    n = len(flats)
    total = flats[0].size
    xcbs = [qk.chunk_blocks(f, n, block)[0] for f in flats]
    nb = xcbs[0].shape[1]
    sig = list(sigma) if sigma is not None else list(range(n))

    def noise(t, rank):
        if not stochastic:
            return None
        return qk.hop_noise(qk.schedule_key(salt, t, rank), nb, block)

    state = [None] * n                      # per position: (q, scale)
    carry = [None] * n                      # per position: hop residual
    err = ([jnp.zeros_like(xcbs[0]) for _ in range(n)]  # per RANK
           if track else None)
    for p in range(n):
        r = sig[p]
        c0 = (p - d) % n
        mine0 = xcbs[r][c0]
        q, s = qk._requant_blocks_jit(mine0, noise(0, r))
        state[p] = (q, s)
        if hop_ef or track:
            res = qk._block_residual_jit(mine0, q, s)
            if hop_ef:
                carry[p] = res
            if track:
                err[r] = err[r].at[c0].set(res)
    for t in range(1, n):
        new = [None] * n
        for p in range(n):
            r = sig[p]
            q, s = state[(p - d) % n]       # payload permuted one step
            c = (p - d * (t + 1)) % n
            mine = xcbs[r][c]
            if hop_ef:
                mine = mine + carry[p]
            q2, s2, res = qk._hop_jnp_jit(
                q, s, mine, noise(t, r), want_resid=hop_ef or track)
            new[p] = (q2, s2)
            if hop_ef:
                carry[p] = res
            if track:
                err[r] = err[r].at[c].set(res)
        state = new
    pieces = [(state[c][0].astype(jnp.float32)
               * state[c][1][:, None]).reshape(-1) for c in range(n)]
    out = jnp.concatenate(pieces)[:total]
    if not track:
        return out, None
    return out, [e.reshape(-1)[:total] for e in err]


def reduce_q8_hop(values, *, block: int = 256, algorithm="ring",
                  inner=None, reverse: bool = False,
                  stochastic: bool = False, hop_ef: bool = False,
                  ef_rounds: int = 1):
    """The quantized fold oracle: reduce per-rank tensors through a
    bit-exact simulation of the in-schedule quantized collective
    (compress/spmd.py) — chunked block-q8 ring reduce-scatter with a
    fresh-block-scale dequantize→accumulate→requantize at every hop,
    composed over the multipath channels of ``algorithm``
    (:func:`multipath_ring_orders`) and the codec's error-feedback
    rounds.

    This is Mode B's side of the compressed Mode A/B parity contract:
    the eager rendezvous backend (compress/eager.py) folds with this
    oracle for the block-q8 codec family, so its results are
    BIT-identical to the compiled SPMD pipeline — including the
    stochastic ``q8_ef_hop`` variant, whose rounding noise is a pure
    function of the schedule (ops/quant_kernels.schedule_key), not of
    call history.  ``reverse`` mirrors the backward pass's swapped
    ``bidir`` channel directions."""
    vals = [jnp.asarray(v) for v in values]
    if not vals:
        raise ValueError("reduce_q8_hop needs at least one value")
    n = len(vals)
    if n == 1:
        return vals[0]
    shape, dtype = vals[0].shape, vals[0].dtype
    flats = [jnp.asarray(v, jnp.float32).reshape(-1) for v in vals]
    total = flats[0].size
    orders = multipath_ring_orders(n, algorithm, inner=inner,
                                   reverse=reverse)
    m = multipath_split(total) if len(orders) > 1 else total
    from .ops import quant_kernels as qk

    outs = []
    for k, (sigma, d) in enumerate(orders):
        if k > 0 and m >= total:
            break
        chan = [f[:m] if k == 0 else f[m:] for f in flats]
        out, resids = _sim_quant_ring(chan, block, sigma, d,
                                      qk.ring_salt(0, k), stochastic,
                                      hop_ef, track=ef_rounds > 1)
        for r in range(1, ef_rounds):
            last = r == ef_rounds - 1
            more, resids = _sim_quant_ring(resids, block, sigma, d,
                                           qk.ring_salt(r, k), stochastic,
                                           hop_ef, track=not last)
            out = out + more
        outs.append(out)
    flat_out = outs[0] if len(outs) == 1 else jnp.concatenate(outs)
    return flat_out.reshape(shape).astype(dtype)


# Below this element count the N-1 jnp folds beat the host round-trip of
# the native kernel.  Measured on a host's CPU (8 f32 buffers, round-5
# single-core host, before any chip run): native/jnp seconds were
# 3.5e-4/2.4e-4 at 64Ki elements, 7.5e-4/1.04e-3 at 256Ki, 2.3e-3/3.7e-3
# at 1Mi — the blocked one-pass C fold wins ~1.4-1.6x above the ~128Ki
# crossover, loses to dispatch overhead below it.
_NATIVE_REDUCE_MIN_SIZE = 131072


def _on_cpu(v) -> bool:
    try:
        return all(d.platform == "cpu" for d in v.devices())
    except AttributeError:
        return True  # plain numpy


def reduce_ordered(op: int, values):
    """Reduce a list of per-rank tensors in ascending rank order.

    Fixed linear order => deterministic, reproducible floating-point results
    (the 'MPI reference oracle' for the bit-exactness target in BASELINE.md).
    Large CPU-resident operands take the fused native kernel
    (mpi4torch_tpu/_native), which folds in the identical order in one
    memory pass; the pure-JAX fold is the always-available fallback and is
    bit-equal.
    """
    if not values:
        raise ValueError("reduce_ordered needs at least one value")
    if len(values) > 1:
        first = values[0]
        if (getattr(first, "size", 0) >= _NATIVE_REDUCE_MIN_SIZE
                and all(_on_cpu(v) for v in values)):
            from . import _native
            if _native.available():
                import numpy as np
                res = _native.ordered_reduce(
                    [np.asarray(v) for v in values], op)
                if res is not None:
                    # JAX inputs already carry canonical dtypes, so the
                    # round-trip is lossless; plain-numpy inputs keep their
                    # numpy dtype exactly like the fallback fold would
                    # (jnp.asarray would downcast f64/i64 with x64 off).
                    if any(hasattr(v, "devices") for v in values):
                        return jnp.asarray(res)
                    return res
    out = values[0]
    for v in values[1:]:
        out = combine2(op, out, v)
    return out
