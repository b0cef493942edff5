"""One StableHLO parser for every census in the repo.

The paper's contract — every collective is an AD node whose backward is
itself a collective, with handle machinery encoding cross-rank ordering
the per-rank DAG cannot see — is a *structural* property of the lowered
program, and the repo grew four independent regex readers of that
structure: the scheduled-exposure census (overlap/census.py), the
peak-liveness scan (reshard/census.py), a wire-bytes accounting, and
~45 ad-hoc matchers in tests/test_hlo.py.  This module
replaces the *parsing* layer under all of them with one pass:

:func:`parse_program` turns any lowered program (a ``jax.stages.
Lowered`` or its ``as_text()``/``debug_info=True`` text) into a
:class:`ParsedProgram` carrying

* typed :class:`CollectiveOp` records for every wire op —
  kind, ``replica_groups`` (values AND declared shape),
  ``source_target_pairs``, channel handle, operand/result tensor types,
  payload dtype/bytes, and the named-scope label recovered from the
  debug-info loc table (``mpi4torch.Allreduce.q8``,
  ``mpi4torch.Allreduce_tree.bucket0of3.start``, ...);
* an :class:`OpEvent` stream of EVERY ``stablehlo.*`` op in program
  order with its scope — the substrate of the scheduled-exposure
  census, kept event-for-event identical to the original
  overlap/census.py reader so the recorded exposure fractions stay
  bit-identical;
* the module's ``mhlo.num_partitions`` (the participating axis the
  replica-group lints check partitioning against) and the per-function
  line structure (the liveness scan's scoping rule).

The soundness lints (:mod:`.lints`), the unified accounting passes
(:mod:`.accounting`), and the registry-wide sweep (:mod:`.sweep`,
``python -m mpi4torch_tpu.analyze --sweep``) are all passes over this
parse; its op records are the structural seed for the GC3-style
schedule IR (ROADMAP item 1).
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from functools import cached_property
from typing import Dict, List, Optional, Tuple

__all__ = [
    "COLLECTIVE_KINDS",
    "WIRE_OPS",
    "CollectiveOp",
    "OpEvent",
    "ParsedProgram",
    "bucket_of",
    "dtype_bytes",
    "parse_program",
    "tensor_bytes",
]

# The StableHLO op kinds that put bytes on the wire (or rendezvous
# ranks).  One definition: the exposure census's in-flight-company set,
# the wire-bytes accounting's op table, and the lints' structural
# domain all read it from here.
COLLECTIVE_KINDS = ("all_reduce", "all_gather", "reduce_scatter",
                    "all_to_all", "collective_permute")
WIRE_OPS = frozenset(COLLECTIVE_KINDS)

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2,
    "i64": 8, "ui64": 8, "i32": 4, "ui32": 4,
    "i16": 2, "ui16": 2, "i8": 1, "ui8": 1, "i1": 1,
    "c64": 8, "c128": 16,
}

# Loc-table grammar.  `scope` keeps the semantics the original census
# readers relied on: the leading name string of the op line's loc
# definition (`#locN = loc("jit(..)/../mpi4torch.Allreduce.q8/.."`), an
# inline `loc("...")`, or "" — pure-callsite locs carry Python frames,
# not named-scope paths, and resolving them would silently re-key the
# recorded exposure censuses.
_LOC_DEF = re.compile(r'^#loc(\d+) = loc\("([^"]*)"')
_LOC_REF = re.compile(r"loc\(#loc(\d+)\)")
_LOC_INLINE = re.compile(r'loc\("([^"]*)"')
_OP_KIND = re.compile(r'"?stablehlo\.([a-z_0-9]+)"?')
_BUCKET = re.compile(
    r"mpi4torch\.(?P<op>[A-Za-z_]+)\.bucket(?P<i>\d+)of(?P<n>\d+)"
    r"(?P<rest>(?:\.\w+)*)")
_LABEL = re.compile(r"mpi4torch\.[A-Za-z_0-9.]+")

_NUM_PARTITIONS = re.compile(r"mhlo\.num_partitions = (\d+)")
_COLLECTIVE_HEAD = re.compile(
    r'"?stablehlo\.(all_reduce|all_gather|reduce_scatter|all_to_all|'
    r'collective_permute)"?\(')
_REPLICA_GROUPS = re.compile(
    r"replica_groups = dense<([^>]*)> : tensor<(\d+)x(\d+)xi64>")
_SOURCE_TARGET = re.compile(
    r"source_target_pairs = dense<([^>]*)> : tensor<(\d+)x2xi64>")
_CHANNEL = re.compile(
    r"#stablehlo\.channel_handle<handle = (\d+)")
_SIGNATURE = re.compile(r":\s*\(([^)]*)\)\s*->\s*(.*)$")
_REGION_CLOSE = re.compile(r"^\s*\}\)\s*:")
_TENSOR = re.compile(r"tensor<([^>]*)>")
_FUNC = re.compile(r"func\.func\s+(?:public\s+|private\s+)?@([\w.$-]+)")


def dtype_bytes(element_type: str) -> Optional[int]:
    """Bytes per element of a StableHLO element type (``f32`` -> 4), or
    None for token/tuple/unknown types that carry no priceable
    buffer."""
    return _DTYPE_BYTES.get(element_type)


def tensor_bytes(desc: str) -> int:
    """Bytes of a ``tensor<...>`` type description (``8x128xf32``).
    Token/tuple/unknown element types and dynamic dims carry 0 — they
    have no buffer the accountings could price.  (A zero-sized dim is
    a legitimate 0, not unknown — :func:`dtype_bytes` distinguishes.)"""
    parts = desc.replace(" ", "").split("x")
    n = _DTYPE_BYTES.get(parts[-1])
    if n is None:
        return 0
    for d in parts[:-1]:
        if not d.isdigit():
            return 0
        n *= int(d)
    return n


def bucket_of(scope: str):
    """``(op, bucket, total, phase)`` of the outermost
    ``mpi4torch.<Op>.bucket<i>of<n>[...]`` span in a location path, or
    None — the bucket_scope grammar of utils/profiling.py, shared by
    the exposure census and the split-phase lints."""
    m = _BUCKET.search(scope)
    if m is None:
        return None
    rest = m.group("rest").split(".")
    phase = ("start" if "start" in rest
             else "wait" if "wait" in rest else None)
    return (m.group("op"), int(m.group("i")), int(m.group("n")), phase)


def _parse_dense_int(literal: str, rows: int, cols: int
                     ) -> Tuple[Tuple[int, ...], ...]:
    """A `dense<...>` integer literal as row tuples: bracketed tables
    (``[[0, 1], [2, 3]]``) verbatim, splats (``dense<0>``) expanded to
    the declared shape."""
    body = literal.strip()
    if body.startswith("["):
        return tuple(
            tuple(int(v) for v in re.findall(r"-?\d+", row))
            for row in re.findall(r"\[([^\[\]]*)\]", body))
    v = int(body)
    return tuple((v,) * cols for _ in range(rows))


@dataclass(frozen=True)
class OpEvent:
    """One ``stablehlo.*`` op occurrence in program order."""
    line: int          # 0-based line index in the lowered text
    kind: str          # op mnemonic ("all_reduce", "add", ...)
    scope: str         # named-scope path of the op line's loc, or ""

    @property
    def bucket(self):
        return bucket_of(self.scope)


@dataclass(frozen=True)
class CollectiveOp:
    """A typed record of one wire collective in a lowered program."""
    kind: str                                    # COLLECTIVE_KINDS entry
    line: int                                    # head-line index
    scope: str                                   # named-scope path or ""
    operand_types: Tuple[str, ...]               # tensor<..> descs
    result_types: Tuple[str, ...]
    replica_groups: Optional[Tuple[Tuple[int, ...], ...]] = None
    group_shape: Optional[Tuple[int, int]] = None   # declared RxC
    source_target_pairs: Optional[Tuple[Tuple[int, int], ...]] = None
    channel: Optional[int] = None

    @property
    def dtype(self) -> Optional[str]:
        """Element type of the payload (first operand)."""
        if not self.operand_types:
            return None
        return self.operand_types[0].replace(" ", "").split("x")[-1]

    @property
    def payload_bytes(self) -> int:
        """Bytes of the first operand — what one device contributes."""
        return tensor_bytes(self.operand_types[0]) \
            if self.operand_types else 0

    @property
    def group_size(self) -> Optional[int]:
        """Participants per replica group (the declared column count —
        the ``s`` of the standard ring wire accountings)."""
        return self.group_shape[1] if self.group_shape else None

    @property
    def label(self) -> Optional[str]:
        """The outermost ``mpi4torch.*`` span of the scope path (e.g.
        ``mpi4torch.Allreduce.q8``), or None."""
        m = _LABEL.search(self.scope)
        return m.group(0) if m else None

    @property
    def bucket(self):
        return bucket_of(self.scope)


@dataclass
class ParsedProgram:
    """The shared parse every analysis pass consumes."""
    text: str
    lines: List[str] = field(repr=False)
    num_partitions: Optional[int]
    events: Tuple[OpEvent, ...] = field(repr=False)
    collectives: Tuple[CollectiveOp, ...]

    def census(self) -> Dict[str, int]:
        """Collective-kind -> occurrence count, every kind present (the
        tests/test_hlo.py ``census()``/``only()`` shape)."""
        out = {k: 0 for k in COLLECTIVE_KINDS}
        for op in self.collectives:
            out[op.kind] += 1
        return out

    def ops(self, kind: Optional[str] = None,
            dtype: Optional[str] = None) -> Tuple[CollectiveOp, ...]:
        """Collective records filtered by kind and/or payload dtype."""
        got = self.collectives
        if kind is not None:
            got = tuple(op for op in got if op.kind == kind)
        if dtype is not None:
            got = tuple(op for op in got if op.dtype == dtype)
        return got

    def scopes(self) -> Tuple[str, ...]:
        """Every distinct non-empty scope path, in first-seen order."""
        seen: Dict[str, None] = {}
        for ev in self.events:
            if ev.scope:
                seen.setdefault(ev.scope)
        return tuple(seen)

    @cached_property
    def function_chunks(self) -> List[List[str]]:
        """The text split at ``func.func`` boundaries — SSA values are
        per-function scopes, so the liveness scan censuses chunk by
        chunk (the reshard/census.py scoping rule)."""
        chunks: List[List[str]] = []
        cur: List[str] = []
        for ln in self.lines:
            if "func.func" in ln and cur:
                chunks.append(cur)
                cur = []
            cur.append(ln)
        if cur:
            chunks.append(cur)
        return chunks


def _as_text(lowered_or_text, debug_info: bool = True) -> str:
    if isinstance(lowered_or_text, str):
        return lowered_or_text
    return lowered_or_text.as_text(debug_info=debug_info)


def _scope_of(line: str, loc_names: Dict[str, str]) -> str:
    ref = _LOC_REF.search(line)
    scope = loc_names.get(ref.group(1), "") if ref is not None else ""
    if not scope:
        im = _LOC_INLINE.search(line)
        scope = im.group(1) if im is not None else ""
    return scope


def _collective_at(lines: List[str], idx: int, kind: str,
                   loc_names: Dict[str, str]) -> CollectiveOp:
    """Assemble the typed record of the collective whose head is on
    ``lines[idx]``.  Attributes live on the head line; ``all_reduce``/
    ``reduce_scatter`` carry a multi-line reduction region, so their
    type signature (and authoritative loc) sit on the ``}) :`` closing
    line."""
    head = lines[idx]
    sig_line = head
    if _SIGNATURE.search(_strip_loc(head)) is None:
        for j in range(idx + 1, len(lines)):
            if _REGION_CLOSE.match(lines[j]):
                sig_line = lines[j]
                break

    groups = shape = None
    m = _REPLICA_GROUPS.search(head)
    if m is not None:
        shape = (int(m.group(2)), int(m.group(3)))
        groups = _parse_dense_int(m.group(1), *shape)
    pairs = None
    m = _SOURCE_TARGET.search(head)
    if m is not None:
        pairs = tuple(
            (int(a), int(b))
            for a, b in _parse_dense_int(m.group(1), int(m.group(2)), 2))
    cm = _CHANNEL.search(head)
    channel = int(cm.group(1)) if cm is not None else None

    operand_types: Tuple[str, ...] = ()
    result_types: Tuple[str, ...] = ()
    sm = _SIGNATURE.search(_strip_loc(sig_line))
    if sm is not None:
        operand_types = tuple(
            t.group(1) for t in _TENSOR.finditer(sm.group(1)))
        result_types = tuple(
            t.group(1) for t in _TENSOR.finditer(sm.group(2)))

    scope = _scope_of(head, loc_names)
    if not scope and sig_line is not head:
        scope = _scope_of(sig_line, loc_names)
    return CollectiveOp(
        kind=kind, line=idx, scope=scope,
        operand_types=operand_types, result_types=result_types,
        replica_groups=groups, group_shape=shape,
        source_target_pairs=pairs, channel=channel)


def _strip_loc(line: str) -> str:
    """Drop the trailing ``loc(...)`` so the signature regex's greedy
    tail captures only type text."""
    i = line.rfind(" loc(")
    return line[:i] if i >= 0 else line


def parse_program(lowered_or_text,
                  debug_info: bool = True) -> ParsedProgram:
    """Parse a lowered program (``jax.stages.Lowered`` or its text)
    into the shared :class:`ParsedProgram`.  ``debug_info`` only
    matters when a ``Lowered`` is passed: the named-scope labels
    (bucket spans, codec suffixes) live in the debug-info loc table, so
    scope-reading passes need it on (the default)."""
    text = _as_text(lowered_or_text, debug_info=debug_info)
    lines = text.splitlines()

    loc_names: Dict[str, str] = {}
    for ln in lines:
        m = _LOC_DEF.match(ln)
        if m is not None:
            loc_names[m.group(1)] = m.group(2)

    mp = _NUM_PARTITIONS.search(text)
    num_partitions = int(mp.group(1)) if mp is not None else None

    events: List[OpEvent] = []
    collectives: List[CollectiveOp] = []
    for idx, ln in enumerate(lines):
        if ln.startswith("#loc"):
            continue
        km = _OP_KIND.search(ln)
        if km is None:
            continue
        events.append(OpEvent(line=idx, kind=km.group(1),
                              scope=_scope_of(ln, loc_names)))
        cm = _COLLECTIVE_HEAD.search(ln)
        if cm is not None:
            collectives.append(
                _collective_at(lines, idx, cm.group(1), loc_names))

    return ParsedProgram(
        text=text, lines=lines, num_partitions=num_partitions,
        events=tuple(events), collectives=tuple(collectives))
