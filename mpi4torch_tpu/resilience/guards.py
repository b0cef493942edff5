"""Integrity guards: non-finite payload checks + compressed-wire checksums.

Two guard families, both **off by default with a zero-overhead off
path** (tests/test_resilience.py holds that the Mode A lowering is
bit-identical to a guard-less build when off):

* ``config.comm_finite_guard`` ∈ {"off", "warn", "raise"} — non-finite
  (NaN/Inf) payload checks.  On the eager backend
  (:func:`check_contributions`) the check runs over every rank's
  contribution at the rendezvous decode site, so the offending rank is
  *named* in the :class:`~mpi4torch_tpu.IntegrityError` /
  :class:`IntegrityWarning` instead of folding silently into everyone's
  gradients.  On the SPMD backend (:func:`spmd_finite_value`) the check
  lowers to an ``is_finite``+reduce feeding a host debug callback —
  "warn" warns, "raise" raises from the callback (surfacing at the
  runtime's next sync point; compiled programs cannot unwind
  mid-schedule) — and every violation is additionally recorded in a
  host-side ledger (:func:`last_violation`) that tests and training
  loops can poll deterministically.

* ``config.comm_wire_checksum`` — a CRC32 leg on the compressed eager
  wire format (compress/eager.py): each encoded payload ships with the
  checksum of its wire bytes, decode verifies per rank, and a mismatch
  (e.g. an injected ``bitflip`` on the int8 blocks) raises
  :class:`~mpi4torch_tpu.IntegrityError` naming the corrupt
  contributor.  Off keeps the wire tuple — and the Mode B signature —
  exactly as before.
"""

from __future__ import annotations

import functools
import threading
import warnings
from typing import List, Optional, Sequence

from .. import config as _config
from ..runtime import IntegrityError

__all__ = [
    "IntegrityWarning",
    "check_contributions",
    "spmd_finite_value",
    "wire_checksum",
    "verify_wire",
    "last_violation",
    "clear_violations",
]


class IntegrityWarning(RuntimeWarning):
    """Warning class of ``comm_finite_guard="warn"`` — filterable apart
    from generic RuntimeWarnings."""


def _all_finite(tree) -> bool:
    import jax
    import jax.numpy as jnp
    import numpy as np

    for leaf in jax.tree_util.tree_leaves(tree):
        dt = getattr(leaf, "dtype", None)
        if dt is None or getattr(leaf, "size", 0) == 0:
            continue
        if not jnp.issubdtype(dt, jnp.floating):
            continue
        if np.issubdtype(dt, np.floating):
            # Numpy-native float dtypes (f16/f32/f64): check WITHOUT
            # jnp canonicalization — with x64 disabled, jnp.asarray
            # downcasts a float64 payload to f32 and turns
            # huge-but-finite values (1e300) into false Infs, accusing
            # an innocent rank.
            if not np.isfinite(np.asarray(leaf)).all():
                return False
        elif not bool(jnp.isfinite(jnp.asarray(leaf)).all()):
            # ml_dtypes floats (bf16, ...): jnp handles them natively
            # and preserves the dtype.
            return False
    return True


def check_contributions(vals: Sequence, opname: str) -> None:
    """Mode B finite guard over a rank-ordered contribution list (the
    rendezvous decode site): index ``i`` of ``vals`` is rank ``i`` —
    every call site assembles the full rank-ordered list.  No-op when
    the guard is off.  Every rank holds the same list, so the raise is
    symmetric across rank threads — no secondary barrier aborts."""
    mode = _config.comm_finite_guard()
    if mode == "off":
        return
    bad = []
    for i, v in enumerate(vals):
        if not _all_finite(v):
            bad.append(i)
    if not bad:
        return
    msg = (f"non-finite payload from rank(s) {sorted(bad)} in {opname} "
           f"(comm_finite_guard={mode!r}): a corrupt contribution would "
           "fold into every rank's result")
    _record(opname, mode, bad)
    if mode == "raise":
        raise IntegrityError(msg, ranks=bad)
    warnings.warn(msg, IntegrityWarning, stacklevel=2)


# ---------------------------------------------------------------- Mode A

# Host-side violation ledger: the deterministic observation surface for
# the SPMD guard (exception plumbing out of a compiled program is
# backend-dependent; the ledger is not).  Guarded by a lock — debug
# callbacks may fire from runtime threads.
_violations: List[dict] = []
_viol_lock = threading.Lock()


def _record(where: str, mode: str, ranks=()) -> None:
    with _viol_lock:
        _violations.append(
            {"where": where, "mode": mode, "ranks": sorted(ranks)})
    # Observability surface (mpi4torch_tpu.obs): violations are rare by
    # definition, so the metric write sits off the guard fast path; the
    # ledger (last_violation) stays the deterministic poll surface.
    from ..obs import metrics as _metrics
    _metrics.inc("integrity_violations_total",
                 help="finite-guard/checksum violations recorded by the "
                      "resilience guards")


def last_violation() -> Optional[dict]:
    """The most recent finite-guard violation record (or None) — poll
    after ``jax.block_until_ready`` for Mode A, immediately for Mode B."""
    with _viol_lock:
        return _violations[-1] if _violations else None


def clear_violations() -> None:
    with _viol_lock:
        _violations.clear()


def _spmd_report(ok, *, where: str, mode: str) -> None:
    if bool(ok):
        return
    _record(where, mode)
    msg = (f"non-finite payload entering {where} "
           f"(comm_finite_guard={mode!r})")
    if mode == "raise":
        raise IntegrityError(msg)
    warnings.warn(msg, IntegrityWarning, stacklevel=2)


def spmd_finite_value(x, where: str):
    """Mode A finite guard hook: called at trace time on a collective's
    input value.  ``comm_finite_guard="off"`` (default) returns ``x``
    untouched — ZERO ops added, the lowering is bit-identical to a
    guard-less build (``config.thresholds_fingerprint`` carries the mode,
    so toggling retraces).  "warn"/"raise" add an ``is_finite`` + all()
    reduce feeding a host callback; violations land in the host ledger
    (:func:`last_violation`) and, for "raise", the callback raises
    (surfacing at the runtime's next synchronization — compiled
    schedules cannot unwind mid-flight, which is why the ledger, not the
    exception, is the contract here)."""
    mode = _config.comm_finite_guard()
    if mode == "off":
        return x
    import jax
    import jax.numpy as jnp

    xa = jnp.asarray(x)
    if not jnp.issubdtype(xa.dtype, jnp.floating):
        return x
    ok = jnp.isfinite(xa).all()
    jax.debug.callback(
        functools.partial(_spmd_report, where=where, mode=mode), ok)
    return x


# ------------------------------------------------------------- checksums

def wire_checksum(payload) -> int:
    """CRC32 over the wire bytes of an encoded payload's leaves (pytree
    canonical order — deterministic).  Host-side: the compressed eager
    wire is concrete arrays at the rendezvous."""
    import zlib

    import jax
    import numpy as np

    c = 0
    for leaf in jax.tree_util.tree_leaves(payload):
        c = zlib.crc32(np.ascontiguousarray(np.asarray(leaf)).tobytes(), c)
    return c & 0xFFFFFFFF


def verify_wire(items: Sequence, opname: str) -> List:
    """Verify a rank-ordered list of checksummed wire tuples
    ``(meta, payload, crc)``; returns the ``(meta, payload)`` list.
    The CRC covers META AND PAYLOAD — the block scales in a codec's
    meta steer the decode just as much as the quantized blocks, so a
    corrupted scale must not pass verification.  A mismatch raises
    :class:`~mpi4torch_tpu.IntegrityError` naming the corrupt
    contributor(s).  Symmetric: every rank verifies the same list."""
    bad = []
    out = []
    for r, (meta, payload, crc) in enumerate(items):
        if wire_checksum((meta, payload)) != crc:
            bad.append(r)
        out.append((meta, payload))
    if bad:
        raise IntegrityError(
            f"compressed wire checksum mismatch for rank(s) {sorted(bad)} "
            f"in {opname}: the encoded payload was corrupted in transit "
            "(comm_wire_checksum guard)", ranks=bad)
    return out
