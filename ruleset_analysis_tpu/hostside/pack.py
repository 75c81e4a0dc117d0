"""Packing: Rulesets -> device-ready rule tensor; parsed lines -> tuple batches.

This is the rebuilt L1->L3 boundary (SURVEY.md §2): where the reference
pickles per-firewall ACL dicts and ships them to every Hadoop map task, we
pack every firewall's expanded ACEs into ONE flat uint32 rule matrix that
lives in device HBM, plus small host-side lookup tables.

Rule matrix layout (``[R, RULE_COLS] uint32``, row order = global config
order, which is load-bearing for first-match parity):

  col 0  acl_gid   — global ACL id (firewall+ACL resolved on host)
  col 1  proto_lo  | 2 proto_hi
  col 3  src_lo    | 4 src_hi
  col 5  sport_lo  | 6 sport_hi
  col 7  dst_lo    | 8 dst_hi
  col 9  dport_lo  | 10 dport_hi
  col 11 key_id    — id of the configured rule this expanded row belongs to

Padding rows carry ``acl_gid = NO_ACL`` and can never match.

Tuple batch layout (``[B, TUPLE_COLS] uint32``):

  col 0 acl_gid | 1 proto | 2 src | 3 sport | 4 dst | 5 dport | 6 valid

Key space: keys ``0..n_rules-1`` are configured rules in global order;
keys ``n_rules..n_rules+n_acls-1`` are each ACL's implicit deny.  The
unused-rule report is "configured-rule keys with zero hits" (SURVEY.md §4.5).
"""

from __future__ import annotations

import dataclasses
import json

import numpy as np

from ..errors import AnalysisError
from .aclparse import Ruleset
from .syslog import ParsedLine, parse_line

RULE_COLS = 12
TUPLE_COLS = 7
#: Wire-format columns (see :func:`compact_batch`): the host->device feed
#: is the e2e bottleneck on PCIe-starved links, so batches cross the wire
#: bit-packed at 16 B/line instead of the working layout's 28 B/line.
WIRE_COLS = 4
#: WEIGHTED wire columns: the wire layout plus one trailing uint32
#: weights row (20 B/row).  A coalesced batch ships every distinct
#: evaluation tuple once with its repetition count; the device step
#: reads the weights row as its valid/weight plane (pipeline.batch_cols),
#: so registers update exactly as the uncoalesced batch would.
WIREW_COLS = 5

#: Rule-axis block size for the match kernel's scan path (defined here,
#: jax-free, so host-side packing/stacking and the device kernel share
#: one padding granularity).  Keeps each [B, RULE_BLOCK] predicate tile
#: comfortably inside VMEM at B = 64k.
RULE_BLOCK = 512

# rule matrix columns
R_ACL, R_PLO, R_PHI, R_SLO, R_SHI, R_SPLO, R_SPHI, R_DLO, R_DHI, R_DPLO, R_DPHI, R_KEY = range(12)
# tuple columns
T_ACL, T_PROTO, T_SRC, T_SPORT, T_DST, T_DPORT, T_VALID = range(7)
# wire columns (compact_batch): src | dst | sport<<16|dport | proto<<24|valid<<23|acl
W_SRC, W_DST, W_PORTS, W_META = range(4)
#: weights row of the WEIGHTED wire layout (coalesced batches)
W_WEIGHT = 4

# ---------------------------------------------------------------------------
# IPv6 family (DESIGN.md "IPv6 position"): 128-bit addresses as 4 uint32
# big-endian limbs.  v6 rows/tuples live in SEPARATE tensors so the v4 hot
# path is untouched; splitting by family preserves first-match order
# because a packet can only match ACEs of its own family (aclparse.Ace).
# Rule keys are shared across families — one report, one key universe.
# ---------------------------------------------------------------------------

RULE6_COLS = 24
TUPLE6_COLS = 13

# v6 rule matrix columns: acl | proto lo/hi | src lo limbs | src hi limbs
# | sport lo/hi | dst lo limbs | dst hi limbs | dport lo/hi | key
R6_ACL = 0
R6_PLO, R6_PHI = 1, 2
R6_SLO = 3   # ..6   (big-endian limbs: col R6_SLO+i is bits 127-32i..96-32i)
R6_SHI = 7   # ..10
R6_SPLO, R6_SPHI = 11, 12
R6_DLO = 13  # ..16
R6_DHI = 17  # ..20
R6_DPLO, R6_DPHI = 21, 22
R6_KEY = 23

# v6 tuple columns
T6_ACL = 0
T6_PROTO = 1
T6_SRC = 2   # ..5
T6_SPORT = 6
T6_DST = 7   # ..10
T6_DPORT = 11
T6_VALID = 12

#: v6 wire columns (DESIGN.md "wire format v2", 40 B/line): the address
#: limbs ride uncompressed, ports pack as sport<<16|dport and meta as
#: proto<<24|valid<<23|acl — the same two packed words as the v4 format,
#: so the device unpack is the same three VPU shifts.
WIRE6_COLS = 10
W6_SRC = 0   # ..3
W6_DST = 4   # ..7
W6_PORTS = 8
W6_META = 9
#: weighted v6 wire layout: WIRE6_COLS plus a trailing weights row
#: (44 B/row; same contract as the v4 WIREW_COLS layout).
WIRE6W_COLS = 11
W6_WEIGHT = 10


def compact_batch6(batch6: np.ndarray) -> np.ndarray:
    """Column-major working v6 batch ``[TUPLE6_COLS, B]`` -> ``[WIRE6_COLS, B]``."""
    u32 = np.uint32
    out = np.empty((WIRE6_COLS, batch6.shape[1]), dtype=u32)
    out[W6_SRC:W6_SRC + 4] = batch6[T6_SRC:T6_SRC + 4]
    out[W6_DST:W6_DST + 4] = batch6[T6_DST:T6_DST + 4]
    out[W6_PORTS] = (batch6[T6_SPORT] << u32(16)) | (batch6[T6_DPORT] & u32(0xFFFF))
    out[W6_META] = (
        (batch6[T6_PROTO] << u32(24))
        | ((batch6[T6_VALID] & u32(1)) << u32(23))
        | (batch6[T6_ACL] & u32(WIRE_MAX_ACLS - 1))
    )
    return out


def expand_batch6(wire6: np.ndarray) -> np.ndarray:
    """Inverse of :func:`compact_batch6` (tests / debugging).

    Accepts the plain ``[WIRE6_COLS, B]`` layout and the weighted
    ``[WIRE6W_COLS, B]`` layout (T6_VALID then carries the weights).
    """
    u32 = np.uint32
    out = np.zeros((TUPLE6_COLS, wire6.shape[1]), dtype=u32)
    meta = wire6[W6_META]
    out[T6_SRC:T6_SRC + 4] = wire6[W6_SRC:W6_SRC + 4]
    out[T6_DST:T6_DST + 4] = wire6[W6_DST:W6_DST + 4]
    out[T6_SPORT] = wire6[W6_PORTS] >> u32(16)
    out[T6_DPORT] = wire6[W6_PORTS] & u32(0xFFFF)
    out[T6_PROTO] = meta >> u32(24)
    if wire6.shape[0] == WIRE6W_COLS:
        out[T6_VALID] = wire6[W6_WEIGHT]
    else:
        out[T6_VALID] = (meta >> u32(23)) & u32(1)
    out[T6_ACL] = meta & u32(WIRE_MAX_ACLS - 1)
    return out


def u128_limbs(v: int) -> tuple[int, int, int, int]:
    """128-bit int -> 4 big-endian uint32 limbs."""
    m = 0xFFFFFFFF
    return ((v >> 96) & m, (v >> 64) & m, (v >> 32) & m, v & m)


def limbs_u128(l0: int, l1: int, l2: int, l3: int) -> int:
    return (int(l0) << 96) | (int(l1) << 64) | (int(l2) << 32) | int(l3)


#: v6 talker digest->address map size cap (~6 MB of host dict at the
#: cap); past it new v6 sources keep full analysis fidelity but render
#: as raw ``v6#`` digests in the talker section.  One knob for every
#: source tier (text / native / feeder / wire).
V6_DIGEST_CAP = 1 << 18


def fold_src32_np(limbs: np.ndarray) -> np.ndarray:
    """Vectorized :func:`fold_src32_host` over ``[4, n]`` uint32 limbs."""
    u32 = np.uint32
    with np.errstate(over="ignore"):
        h = limbs[0] * u32(0x9E3779B1)
        h = (h ^ limbs[1]) * u32(0x85EBCA77)
        h = (h ^ limbs[2]) * u32(0xC2B2AE3D)
        h = (h ^ limbs[3]) * u32(0x27D4EB2F)
    return h ^ (h >> u32(15))


def fold_src32_host(v: int) -> int:
    """Host twin of ops.match6.fold_src32 (the v6 sketch identity).

    Must stay bit-identical to the device fold: the stream driver records
    digest -> address so reports can render v6 talkers as real addresses.
    tests/test_match6.py pins host/device agreement.
    """
    m = 0xFFFFFFFF
    l0, l1, l2, l3 = u128_limbs(v)
    h = (l0 * 0x9E3779B1) & m
    h = ((h ^ l1) * 0x85EBCA77) & m
    h = ((h ^ l2) * 0xC2B2AE3D) & m
    h = ((h ^ l3) * 0x27D4EB2F) & m
    return h ^ (h >> 15)


class V6DigestFill:
    """Fills a v6 talker map (fold digest -> 128-bit source) a batch at a time.

    The map keeps what a per-row ``setdefault`` loop in stream order would
    keep: the first source seen for each digest, in first-seen order, until
    it holds :data:`V6_DIGEST_CAP` entries.  It stays a plain dict for its
    readers; beside it a sorted array of its keys answers "seen before?" for
    a whole batch at once, so Python only builds the address of a digest
    the map lacks.
    """

    def __init__(self, dig: dict[int, int]):
        self.dig = dig
        self._known = np.empty(0, dtype=np.uint32)  # sorted keys of dig

    def add(self, limbs: np.ndarray) -> None:
        """Fold ``[4, n]`` uint32 source limbs, in stream order, into the map."""
        dig = self.dig
        room = V6_DIGEST_CAP - len(dig)
        if room <= 0 or not limbs.shape[1]:
            return
        known = self._known
        if known.size != len(dig):  # entries put in by a resume's restore
            known = np.sort(np.fromiter(dig, dtype=np.uint32, count=len(dig)))
        folds = fold_src32_np(limbs)
        # a header repeats in runs: keep the first row of each run
        runs = np.empty(folds.size, dtype=bool)
        runs[0] = True
        np.not_equal(folds[1:], folds[:-1], out=runs[1:])
        rows = np.flatnonzero(runs)
        f = folds[rows]
        if known.size:
            # look the batch's digests up in sorted order (a sorted search
            # walks the known keys in order: several times faster)
            s = np.sort(f)
            s = np.unique(s[~_in_sorted(known, s)])  # digests new to the map
            new = _in_sorted(s, f) if s.size else np.zeros(f.size, dtype=bool)
            rows, f = rows[new], f[new]
        if f.size:
            # first row of each new digest, in stream order, up to the cap
            first = np.sort(np.unique(f, return_index=True)[1])[:room]
            rows, f = rows[first], f[first]
            l0, l1, l2, l3 = limbs[:, rows].tolist()
            dig.update(zip(f.tolist(), [
                (a << 96) | (b << 64) | (c << 32) | d
                for a, b, c, d in zip(l0, l1, l2, l3)
            ]))
            f.sort()
            known = np.insert(known, np.searchsorted(known, f), f)
        self._known = known

    def add_rows(self, rows6) -> None:
        """:meth:`add` over ``[n, TUPLE6_COLS]`` v6 tuple rows."""
        if len(rows6):
            self.add(np.asarray(rows6, dtype=np.uint32)[:, T6_SRC:T6_SRC + 4].T)


def _in_sorted(keys: np.ndarray, v: np.ndarray) -> np.ndarray:
    """Mask of the entries of ``v`` found in the sorted, non-empty ``keys``."""
    return keys[np.minimum(np.searchsorted(keys, v), keys.size - 1)] == v


#: acl gid budget in the wire meta word: 23 bits (proto takes 8, valid 1).
WIRE_MAX_ACLS = 1 << 23

NO_ACL = np.uint32(0xFFFFFFFF)


@dataclasses.dataclass
class KeyMeta:
    """Report-facing identity of one count key."""

    firewall: str
    acl: str
    index: int  # 1-based rule position; 0 for the ACL's implicit deny
    text: str
    implicit_deny: bool = False
    #: PERMIT(1)/DENY(0) of the configured entry, or -1 when unknown (a
    #: packed artifact written before the static-analysis plane).  The
    #: action never affects matching/counting — only the analyzer's
    #: redundant-vs-conflict split reads it, and it degrades to the
    #: action-free "shadowed" verdict on -1.
    action: int = -1


@dataclasses.dataclass
class PackedRuleset:
    """The packed, device-shippable form of one or more firewalls' rulesets."""

    rules: np.ndarray  # [R, RULE_COLS] uint32
    n_rules: int  # number of configured-rule keys
    n_acls: int
    key_meta: list[KeyMeta]  # len == n_keys
    acl_gid: dict[tuple[str, str], int]  # (firewall, acl name) -> gid
    deny_key: np.ndarray  # [n_acls] uint32: acl_gid -> implicit-deny key
    bindings: dict[tuple[str, str], int]  # (firewall, iface) -> acl_gid ('in')
    #: (firewall, iface) -> acl_gid for ``out``-direction access-groups;
    #: connection messages are evaluated against the egress interface's
    #: out ACL in addition to the ingress in ACL.
    bindings_out: dict[tuple[str, str], int] = dataclasses.field(default_factory=dict)
    #: Lenient-parse skips carried from the Rulesets: (firewall, lineno,
    #: reason) per unsupported config entry — surfaced in the analysis
    #: report so a packed ruleset can't silently hide that its source
    #: config wasn't fully parsed.
    parse_skips: list[tuple[str, int, str]] = dataclasses.field(default_factory=list)
    #: [R6, RULE6_COLS] uint32 — the IPv6 ACE rows (4x uint32 address
    #: limbs), sharing the v4 rows' key universe.  Empty ([0, RULE6_COLS])
    #: for pure-v4 rulesets, in which case the device v6 path never runs.
    rules6: np.ndarray | None = None

    def __post_init__(self):
        if self.rules6 is None:
            self.rules6 = np.zeros((0, RULE6_COLS), dtype=np.uint32)

    @property
    def has_v6(self) -> bool:
        return self.rules6.shape[0] > 0

    @property
    def n_keys(self) -> int:
        return self.n_rules + self.n_acls

    def key_name(self, key: int) -> str:
        m = self.key_meta[key]
        tag = "implicit-deny" if m.implicit_deny else str(m.index)
        return f"{m.firewall} {m.acl} {tag}"


def pack_rulesets(rulesets: list[Ruleset], pad_rules_to: int | None = None) -> PackedRuleset:
    """Pack parsed rulesets into the flat rule matrix + key universe."""
    acl_gid: dict[tuple[str, str], int] = {}
    key_meta: list[KeyMeta] = []
    rows: list[list[int]] = []
    bindings: dict[tuple[str, str], int] = {}
    bindings_out: dict[tuple[str, str], int] = {}

    for rs in rulesets:
        for acl in rs.acls:
            acl_gid[(rs.firewall, acl)] = len(acl_gid)
    if len(acl_gid) > WIRE_MAX_ACLS:
        raise ValueError(
            f"{len(acl_gid)} ACLs exceed the wire format's {WIRE_MAX_ACLS} "
            "acl-gid budget (23 bits of the packed meta word)"
        )

    rows6: list[list[int]] = []
    for rs in rulesets:
        for acl, rules in rs.acls.items():
            gid = acl_gid[(rs.firewall, acl)]
            for rule in rules:
                key = len(key_meta)
                key_meta.append(
                    KeyMeta(
                        firewall=rs.firewall, acl=acl, index=rule.index,
                        text=rule.text,
                        # one config line = one action; every ACE agrees
                        action=rule.aces[0].action if rule.aces else -1,
                    )
                )
                for a in rule.aces:
                    if a.family == 6:
                        rows6.append(
                            [
                                gid,
                                a.proto_lo,
                                a.proto_hi,
                                *u128_limbs(a.src_lo),
                                *u128_limbs(a.src_hi),
                                a.sport_lo,
                                a.sport_hi,
                                *u128_limbs(a.dst_lo),
                                *u128_limbs(a.dst_hi),
                                a.dport_lo,
                                a.dport_hi,
                                key,
                            ]
                        )
                        continue
                    rows.append(
                        [
                            gid,
                            a.proto_lo,
                            a.proto_hi,
                            a.src_lo,
                            a.src_hi,
                            a.sport_lo,
                            a.sport_hi,
                            a.dst_lo,
                            a.dst_hi,
                            a.dport_lo,
                            a.dport_hi,
                            key,
                        ]
                    )
        for (iface, direction), acl in rs.bindings.items():
            if (rs.firewall, acl) not in acl_gid:
                continue
            gid = acl_gid[(rs.firewall, acl)]
            if direction == "in":
                bindings[(rs.firewall, iface)] = gid
            else:
                bindings_out[(rs.firewall, iface)] = gid

    n_rules = len(key_meta)
    n_acls = len(acl_gid)
    deny_key = np.zeros(max(n_acls, 1), dtype=np.uint32)
    for (fw, acl), gid in acl_gid.items():
        deny_key[gid] = n_rules + gid
        key_meta.append(
            KeyMeta(
                firewall=fw, acl=acl, index=0, text="<implicit deny>",
                implicit_deny=True, action=0,
            )
        )

    parse_skips = [
        (rs.firewall, lineno, reason)
        for rs in rulesets
        for lineno, reason, _line in rs.skipped
    ]

    r = len(rows)
    pad_to = max(pad_rules_to or 0, r, 1)
    mat = np.full((pad_to, RULE_COLS), 0, dtype=np.uint32)
    mat[:, R_ACL] = NO_ACL
    if rows:
        mat[:r] = np.asarray(rows, dtype=np.uint32)
    mat6 = (
        np.asarray(rows6, dtype=np.uint32)
        if rows6
        else np.zeros((0, RULE6_COLS), dtype=np.uint32)
    )
    return PackedRuleset(
        rules=mat,
        rules6=mat6,
        n_rules=n_rules,
        n_acls=n_acls,
        key_meta=key_meta,
        acl_gid=acl_gid,
        deny_key=deny_key,
        bindings=bindings,
        bindings_out=bindings_out,
        parse_skips=parse_skips,
    )


# ---------------------------------------------------------------------------
# Wire format: the host->device transfer layout.  Host parsing and tests
# work in the 7-column uint32 layout (one field per lane, convenient to
# index); batches cross PCIe bit-packed into 4 words per
# line, and the device step unpacks with three shifts on the VPU.  Field
# widths: src/dst 32, sport/dport 16, proto 8, valid 1, acl gid 23
# (WIRE_MAX_ACLS; pack_rulesets refuses larger inventories).
# ---------------------------------------------------------------------------


def compact_batch(batch: np.ndarray) -> np.ndarray:
    """Column-major working batch ``[TUPLE_COLS, B]`` -> wire ``[WIRE_COLS, B]``."""
    u32 = np.uint32
    out = np.empty((WIRE_COLS, batch.shape[1]), dtype=u32)
    out[W_SRC] = batch[T_SRC]
    out[W_DST] = batch[T_DST]
    out[W_PORTS] = (batch[T_SPORT] << u32(16)) | (batch[T_DPORT] & u32(0xFFFF))
    out[W_META] = (
        (batch[T_PROTO] << u32(24))
        | ((batch[T_VALID] & u32(1)) << u32(23))
        | (batch[T_ACL] & u32(WIRE_MAX_ACLS - 1))
    )
    return out


def compact_grouped(grouped: np.ndarray) -> np.ndarray:
    """Grouped ``[G, TUPLE_COLS, lane]`` -> wire ``[G, WIRE_COLS, lane]``."""
    g, _, lane = grouped.shape
    flat = compact_batch(grouped.transpose(1, 0, 2).reshape(TUPLE_COLS, g * lane))
    return flat.reshape(WIRE_COLS, g, lane).transpose(1, 0, 2)


def expand_batch(wire: np.ndarray) -> np.ndarray:
    """Inverse of :func:`compact_batch` (tests / debugging).

    Accepts both the plain ``[WIRE_COLS, B]`` layout and the weighted
    ``[WIREW_COLS, B]`` layout; in the weighted case the tuple batch's
    valid column carries the weights (0 = invalid, as everywhere).
    """
    u32 = np.uint32
    out = np.zeros((TUPLE_COLS, wire.shape[1]), dtype=u32)
    meta = wire[W_META]
    out[T_SRC] = wire[W_SRC]
    out[T_DST] = wire[W_DST]
    out[T_SPORT] = wire[W_PORTS] >> u32(16)
    out[T_DPORT] = wire[W_PORTS] & u32(0xFFFF)
    out[T_PROTO] = meta >> u32(24)
    if wire.shape[0] == WIREW_COLS:
        out[T_VALID] = wire[W_WEIGHT]
    else:
        out[T_VALID] = (meta >> u32(23)) & u32(1)
    out[T_ACL] = meta & u32(WIRE_MAX_ACLS - 1)
    return out


# ---------------------------------------------------------------------------
# Flow coalescing (ISSUE 5): ASA flow logs are massively repetitive — the
# same 5-tuple logs 106100/302013/302015 lines over and over — so a batch
# compacts into (unique row, weight) pairs before it ever reaches the
# device.  Every register update is weight-linear (counts/CMS/talker
# scatter-adds take ``weights=``) or idempotent (HLL max), so the final
# report is bit-identical to the uncoalesced path while the dominant
# batch-sized scatters, H2D bytes, and device rows shrink by the
# compaction ratio.  This is the MapReduce combiner (Dean & Ghemawat,
# OSDI'04) applied to a scatter-bound device step.
#
# Representation: weights ride the batch's valid plane.  Tuple layouts
# carry them in T_VALID/T6_VALID (uint32; 0 = invalid); wire layouts grow
# one trailing weights row (WIREW_COLS/WIRE6W_COLS) because the packed
# meta word has only a single valid bit.  Unique rows are emitted in
# FIRST-OCCURRENCE order, so batch position is monotone in the first
# occurrence index — the candidate table's representative scatter-max
# over positions selects the same pair the raw batch's would (DESIGN §11).
# ---------------------------------------------------------------------------


def _np_coalesce(
    mat: np.ndarray, want_first: bool = False
) -> tuple[np.ndarray, np.ndarray | None]:
    """Pure-numpy coalesce of a ``[rows, B]`` uint32 plane.

    The LAST row is the weight/valid plane: zero-weight columns are
    dropped, the remaining columns group by the other rows' values, and
    each group's weights sum.  Returns ``([rows, U], first_idx[U] | None)``
    with unique columns in first-occurrence order.  Bit-identical to the
    native ``asa_coalesce`` fast path (tests pin it).
    """
    w = mat[-1]
    pos = np.flatnonzero(w)
    if pos.size == 0:
        out = np.zeros((mat.shape[0], 0), dtype=np.uint32)
        return out, (np.zeros(0, dtype=np.int64) if want_first else None)
    keys = np.ascontiguousarray(mat[:-1, pos].T)  # [Nv, rows-1]
    view = keys.view([("", np.uint32)] * keys.shape[1]).ravel()
    _, first, inv = np.unique(view, return_index=True, return_inverse=True)
    # summed weights are exact in float64 up to 2^53 raw lines per batch
    sums = np.bincount(inv, weights=w[pos].astype(np.float64))
    order = np.argsort(first, kind="stable")  # first-occurrence order
    out = np.empty((mat.shape[0], order.size), dtype=np.uint32)
    out[:-1] = keys[first[order]].T
    out[-1] = sums[order].astype(np.uint64).astype(np.uint32)
    return out, (pos[first[order]].astype(np.int64) if want_first else None)


def coalesce_cols(
    mat: np.ndarray, want_first: bool = False
) -> tuple[np.ndarray, np.ndarray | None]:
    """Coalesce a ``[rows, B]`` uint32 plane whose LAST row is the weight.

    Uses the native open-addressing hash (``asa_coalesce`` in
    ``native/asaparse.cpp``) when the library loads, else the numpy
    fallback — outputs are bit-identical.  Composes: feeding an already
    weighted plane merges duplicate keys and sums their weights.
    """
    if mat.dtype != np.uint32 or mat.ndim != 2:
        raise ValueError(f"expected [rows, B] uint32, got {mat.shape} {mat.dtype}")
    from . import fastparse

    native = fastparse.native_coalesce(mat, want_first)
    if native is not None:
        return native
    return _np_coalesce(mat, want_first)


def coalesce_batch(batch: np.ndarray) -> np.ndarray:
    """``[TUPLE_COLS, B]`` -> weighted ``[TUPLE_COLS, U]``, U <= B.

    Input valid column may itself carry weights (composes).  Output rows
    are distinct (acl, proto, src, sport, dst, dport) tuples in
    first-occurrence order with T_VALID = summed weight.
    """
    if batch.shape[0] != TUPLE_COLS:
        raise ValueError(f"expected [TUPLE_COLS, B], got {batch.shape}")
    out, _ = coalesce_cols(np.ascontiguousarray(batch))
    return out


def coalesce_batch6(batch6: np.ndarray) -> np.ndarray:
    """v6 twin of :func:`coalesce_batch` (``[TUPLE6_COLS, B]`` in/out)."""
    if batch6.shape[0] != TUPLE6_COLS:
        raise ValueError(f"expected [TUPLE6_COLS, B], got {batch6.shape}")
    out, _ = coalesce_cols(np.ascontiguousarray(batch6))
    return out


def _wire_weighted_view(wire: np.ndarray, cols: int, meta_row: int) -> np.ndarray:
    """Wire batch -> weighted-wire plane (weights synthesized from the
    valid bit when absent), ready for :func:`coalesce_cols`."""
    if wire.shape[0] == cols + 1:
        return np.ascontiguousarray(wire)
    tmp = np.empty((cols + 1, wire.shape[1]), dtype=np.uint32)
    tmp[:cols] = wire
    tmp[cols] = (wire[meta_row] >> np.uint32(23)) & np.uint32(1)
    return tmp


def coalesce_wire(wire: np.ndarray) -> np.ndarray:
    """``[WIRE_COLS, B]`` (or already-weighted ``[WIREW_COLS, B]``) ->
    weighted wire ``[WIREW_COLS, U]``.

    The 4 packed words of a valid row ARE the flow key (their valid bit
    is identically set), so grouping by the stored words is grouping by
    the evaluation tuple.  Zero (padding) columns drop out via weight 0.
    """
    if wire.shape[0] not in (WIRE_COLS, WIREW_COLS):
        raise ValueError(f"expected [WIRE_COLS(+1), B], got {wire.shape}")
    out, _ = coalesce_cols(_wire_weighted_view(wire, WIRE_COLS, W_META))
    return out


def coalesce_wire6(wire6: np.ndarray) -> np.ndarray:
    """v6 twin of :func:`coalesce_wire` (``[WIRE6_COLS(+1), B]`` in)."""
    if wire6.shape[0] not in (WIRE6_COLS, WIRE6W_COLS):
        raise ValueError(f"expected [WIRE6_COLS(+1), B], got {wire6.shape}")
    out, _ = coalesce_cols(_wire_weighted_view(wire6, WIRE6_COLS, W6_META))
    return out


def pad_weighted(mat: np.ndarray, to: int) -> np.ndarray:
    """Zero-pad a weighted plane's column axis to ``to`` columns.

    Zero columns carry weight 0 (and a clear valid bit for wire metas),
    so padding is masked on device exactly like any invalid row.
    """
    if mat.shape[-1] >= to:
        return mat
    out = np.zeros((*mat.shape[:-1], to), dtype=np.uint32)
    out[..., : mat.shape[-1]] = mat
    return out


def compact_batch_w(batch: np.ndarray) -> np.ndarray:
    """Weighted working batch ``[TUPLE_COLS, B]`` -> ``[WIREW_COLS, B]``.

    The weighted twin of :func:`compact_batch`: T_VALID carries a full
    uint32 weight, which rides the extra weights row; the meta valid bit
    is set iff the weight is nonzero (so weight-agnostic consumers — the
    reader sanity checks, expand_batch — keep working).
    """
    u32 = np.uint32
    out = np.empty((WIREW_COLS, batch.shape[1]), dtype=u32)
    out[W_SRC] = batch[T_SRC]
    out[W_DST] = batch[T_DST]
    out[W_PORTS] = (batch[T_SPORT] << u32(16)) | (batch[T_DPORT] & u32(0xFFFF))
    out[W_META] = (
        (batch[T_PROTO] << u32(24))
        | ((batch[T_VALID] > 0).astype(u32) << u32(23))
        | (batch[T_ACL] & u32(WIRE_MAX_ACLS - 1))
    )
    out[W_WEIGHT] = batch[T_VALID]
    return out


def compact_grouped_w(grouped: np.ndarray) -> np.ndarray:
    """Weighted grouped ``[G, TUPLE_COLS, lane]`` -> ``[G, WIREW_COLS, lane]``."""
    g, _, lane = grouped.shape
    flat = compact_batch_w(
        grouped.transpose(1, 0, 2).reshape(TUPLE_COLS, g * lane)
    )
    return flat.reshape(WIREW_COLS, g, lane).transpose(1, 0, 2)


class LinePacker:
    """Parses raw syslog lines into packed tuple batches against a PackedRuleset.

    Lines that don't parse, reference an unknown firewall/ACL, or (for
    connection messages) hit interfaces with no ``access-group`` binding
    are packed with ``valid=0`` — the mapper analog of silently skipping
    non-matching input lines.

    One line can produce MORE than one tuple: a connection message whose
    ingress interface has an ``in`` ACL and whose egress interface has an
    ``out`` ACL is evaluated against both (each evaluation is its own
    tuple row, exactly as the reference mapper would scan both ACLs).
    ``parsed`` counts evaluations emitted; ``skipped`` counts lines that
    produced none.
    """

    def __init__(self, packed: PackedRuleset):
        self.packed = packed
        self.skipped = 0
        self.parsed = 0

    def resolve_gids(self, p: ParsedLine) -> list[int]:
        """ACL gids this line must be evaluated against (possibly two)."""
        if p.acl is not None:
            gid = self.packed.acl_gid.get((p.firewall, p.acl))
            return [] if gid is None else [gid]
        out: list[int] = []
        if p.ingress_if is not None:
            gid = self.packed.bindings.get((p.firewall, p.ingress_if))
            if gid is not None:
                out.append(gid)
        if p.egress_if is not None:
            gid = self.packed.bindings_out.get((p.firewall, p.egress_if))
            if gid is not None:
                out.append(gid)
        return out

    def resolve_acl(self, p: ParsedLine) -> int | None:
        """First resolved gid (compatibility helper; prefer resolve_gids)."""
        gids = self.resolve_gids(p)
        return gids[0] if gids else None

    def pack_parsed2(
        self,
        parsed: list[ParsedLine | None],
        batch_size: int | None = None,
    ) -> tuple[np.ndarray, np.ndarray]:
        """Pack parsed lines into per-family batches.

        Returns ``([B, TUPLE_COLS], [B6, TUPLE6_COLS])`` uint32 batches
        (each padded with valid=0 rows).  The default capacity is one row
        per line — two when any out-direction binding exists, since a
        connection line can then emit two evaluations.  A line's
        evaluations land in its family's batch; both batches share the
        capacity bound (a chunk of N lines can never exceed N (or 2N)
        evaluations across both families combined).
        """
        if batch_size is not None:
            b = batch_size
        else:
            b = (2 if self.packed.bindings_out else 1) * len(parsed)
        out = np.zeros((b, TUPLE_COLS), dtype=np.uint32)
        out6 = np.zeros((b if self.packed.has_v6 else 0, TUPLE6_COLS), dtype=np.uint32)
        i = 0
        i6 = 0
        for p in parsed:
            gids = [] if p is None else self.resolve_gids(p)
            if gids and p.family == 6 and not self.packed.has_v6:
                # a v6 line against a pure-v4 ruleset can only hit the
                # implicit deny; without v6 rows the device path cannot
                # represent it — counted-skip, exactly the pre-v6 behavior
                gids = []
            if not gids:
                self.skipped += 1
                continue
            if i + i6 + len(gids) > b:
                raise ValueError(
                    f"more than batch_size={b} evaluations in chunk; "
                    "feed fewer lines per chunk (each connection line can "
                    "emit two rows when both in and out ACLs are bound)"
                )
            if p.family == 6:
                s = u128_limbs(p.src)
                d = u128_limbs(p.dst)
                for gid in gids:
                    out6[i6] = (gid, p.proto, *s, p.sport, *d, p.dport, 1)
                    i6 += 1
                    self.parsed += 1
            else:
                for gid in gids:
                    out[i] = (gid, p.proto, p.src, p.sport, p.dst, p.dport, 1)
                    i += 1
                    self.parsed += 1
        return out, out6

    def pack_parsed(self, parsed: list[ParsedLine | None], batch_size: int | None = None) -> np.ndarray:
        """v4-only twin of :meth:`pack_parsed2` (the original API).

        Raises :class:`AnalysisError` if any v6 evaluation was packed —
        callers that may see v6 traffic against a v6-capable ruleset must
        use pack_parsed2; silently dropping supported traffic here would
        corrupt the hit counts.
        """
        out, out6 = self.pack_parsed2(parsed, batch_size)
        if out6.size and int(out6[:, T6_VALID].sum()):
            raise AnalysisError(
                "IPv6 evaluations in a v4-only packing call; use "
                "pack_parsed2 (or the streaming driver, which handles "
                "both families)"
            )
        return out

    def pack_lines(self, lines: list[str], batch_size: int | None = None) -> np.ndarray:
        return self.pack_parsed([parse_line(ln) for ln in lines], batch_size)

    def pack_lines2(
        self, lines: list[str], batch_size: int | None = None
    ) -> tuple[np.ndarray, np.ndarray]:
        return self.pack_parsed2([parse_line(ln) for ln in lines], batch_size)


# ---------------------------------------------------------------------------
# Stacked (grouped) form: BASELINE.json config #4 "multi-firewall batched
# ruleset match (vmap over rulesets)".  The flat rule matrix scans EVERY
# firewall's rows for every line; grouping lines by their ACL and stacking
# each ACL's rows into one padded slab drops the per-line cost from
# O(total rows) to O(max slab rows), with the match kernel vmapped over
# the group axis.  Grouping lines host-side is the rebuilt analog of the
# reference's shuffle partitioning (SURVEY.md §3c).
# ---------------------------------------------------------------------------


def stacked_slab_rows(packed: PackedRuleset, rule_block: int = RULE_BLOCK) -> int:
    """Rmax of :func:`stack_rules` without building the slab tensor."""
    g = max(packed.n_acls, 1)
    real = packed.rules[packed.rules[:, R_ACL] != NO_ACL]
    counts = np.bincount(real[:, R_ACL].astype(np.int64), minlength=g) if real.size else np.zeros(g, np.int64)
    rmax = max(int(counts.max()) if counts.size else 0, 1)
    if rmax > rule_block:
        rmax = ((rmax + rule_block - 1) // rule_block) * rule_block
    return rmax


def stack_rules(packed: PackedRuleset, rule_block: int = RULE_BLOCK) -> np.ndarray:
    """[G, Rmax, RULE_COLS] uint32: each ACL's expanded rows, padded.

    Row order inside each slab preserves global config order, so the
    first-match == min-local-row-index invariant carries over.  Rmax is
    padded to ``rule_block`` granularity when any slab exceeds one block
    (the scan path of the match kernel requires it).
    """
    g = max(packed.n_acls, 1)
    real = packed.rules[packed.rules[:, R_ACL] != NO_ACL]
    rmax = stacked_slab_rows(packed, rule_block)
    out = np.zeros((g, rmax, RULE_COLS), dtype=np.uint32)
    out[:, :, R_ACL] = NO_ACL
    fill = np.zeros(g, dtype=np.int64)
    for row in real:
        gid = int(row[R_ACL])
        out[gid, fill[gid]] = row
        fill[gid] += 1
    return out


def stacked_slab_rows6(packed: PackedRuleset, rule_block: int = RULE_BLOCK) -> int:
    """R6max of :func:`stack_rules6` without building the slab tensor."""
    g = max(packed.n_acls, 1)
    real = packed.rules6[packed.rules6[:, R6_ACL] != NO_ACL]
    counts = (
        np.bincount(real[:, R6_ACL].astype(np.int64), minlength=g)
        if real.size
        else np.zeros(g, np.int64)
    )
    rmax = max(int(counts.max()) if counts.size else 0, 1)
    if rmax > rule_block:
        rmax = ((rmax + rule_block - 1) // rule_block) * rule_block
    return rmax


def stack_rules6(packed: PackedRuleset, rule_block: int = RULE_BLOCK) -> np.ndarray:
    """[G, R6max, RULE6_COLS] uint32: each ACL's v6 rows, padded.

    The v6 twin of :func:`stack_rules` (BASELINE config #4 "vmap over
    rulesets"): slab row order preserves global config order so
    first-match == min local row carries over; NO_ACL padding never
    matches.
    """
    g = max(packed.n_acls, 1)
    real = packed.rules6[packed.rules6[:, R6_ACL] != NO_ACL]
    rmax = stacked_slab_rows6(packed, rule_block)
    out = np.zeros((g, rmax, RULE6_COLS), dtype=np.uint32)
    out[:, :, R6_ACL] = NO_ACL
    fill = np.zeros(g, dtype=np.int64)
    for row in real:
        gid = int(row[R6_ACL])
        out[gid, fill[gid]] = row
        fill[gid] += 1
    return out


def group_tuples(batch: np.ndarray, n_groups: int, lane: int) -> np.ndarray:
    """One-shot grouping: [B, TUPLE_COLS] rows -> [G, TUPLE_COLS, lane].

    Valid rows are bucketed by their ACL gid; raises if any bucket
    overflows ``lane`` (streaming callers use :class:`GroupBuffer`, which
    carries overflow to the next grouped batch instead).
    """
    out = np.zeros((n_groups, TUPLE_COLS, lane), dtype=np.uint32)
    valid = batch[batch[:, T_VALID] != 0]  # weighted rows bucket too
    if not valid.size:
        return out
    gids = valid[:, T_ACL].astype(np.int64)
    if gids.max() >= n_groups or np.bincount(gids, minlength=n_groups).max() > lane:
        raise ValueError("bucket overflow: raise lane or use GroupBuffer")
    sv, starts, ends = _bucket_by_gid(valid, gids, n_groups)
    for gid in range(n_groups):
        n = ends[gid] - starts[gid]
        if n:
            out[gid, :, :n] = sv[starts[gid]:ends[gid]].T
    return out


def _bucket_by_gid(valid_rows: np.ndarray, gids: np.ndarray, n_groups: int):
    """Stable-sort rows by gid; return (sorted_rows, starts, ends).

    The STABLE sort is load-bearing: intra-group line order must survive
    bucketing so grouped and flat paths see the same per-group sequences.
    """
    order = np.argsort(gids, kind="stable")
    sg = gids[order]
    starts = np.searchsorted(sg, np.arange(n_groups))
    ends = np.searchsorted(sg, np.arange(n_groups), side="right")
    return valid_rows[order], starts, ends


class GroupBuffer:
    """Streaming per-ACL bucketing with overflow carry.

    Feed packed row-major batches; grouped batches ``[G, TUPLE_COLS,
    lane]`` are emitted whenever some bucket has a full lane (draining all
    buckets simultaneously, shorter ones padded with valid=0), so memory
    stays bounded under group skew.
    """

    def __init__(self, n_groups: int, lane: int):
        self.n_groups = n_groups
        self.lane = lane
        self._q: list[list[np.ndarray]] = [[] for _ in range(n_groups)]
        self._qlen = np.zeros(n_groups, dtype=np.int64)

    def add(self, batch: np.ndarray) -> list[np.ndarray]:
        """Add a [B, TUPLE_COLS] batch; return any full grouped batches.

        Rows whose valid column carries a weight > 1 (coalesced input)
        bucket exactly like plain rows — the weight rides along in the
        row and the grouped compactor (compact_grouped_w) preserves it.
        """
        valid = batch[batch[:, T_VALID] != 0]
        if valid.size:
            gids = valid[:, T_ACL].astype(np.int64)
            sv, starts, ends = _bucket_by_gid(valid, gids, self.n_groups)
            for gid in range(self.n_groups):
                if ends[gid] > starts[gid]:
                    rows = sv[starts[gid]:ends[gid]]
                    self._q[gid].append(rows)
                    self._qlen[gid] += rows.shape[0]
        out = []
        while self._qlen.max(initial=0) >= self.lane:
            out.append(self._emit())
        return out

    def flush(self) -> list[np.ndarray]:
        """Emit remaining buffered lines as (padded) grouped batches."""
        out = []
        while self._qlen.max(initial=0) > 0:
            out.append(self._emit())
        return out

    def _emit(self) -> np.ndarray:
        out = np.zeros((self.n_groups, TUPLE_COLS, self.lane), dtype=np.uint32)
        for gid in range(self.n_groups):
            take = min(self.lane, int(self._qlen[gid]))
            filled = 0
            while filled < take:
                head = self._q[gid][0]
                n = min(head.shape[0], take - filled)
                out[gid, :, filled:filled + n] = head[:n].T
                filled += n
                if n == head.shape[0]:
                    self._q[gid].pop(0)
                else:
                    self._q[gid][0] = head[n:]
            self._qlen[gid] -= take
        return out


# ---------------------------------------------------------------------------
# Serialization (the analog of the reference pickling parser output to disk
# for shipment to map tasks — SURVEY.md §4.1).  JSON + npz: inspectable and
# dependency-free.
# ---------------------------------------------------------------------------


def save_packed(packed: PackedRuleset, path_prefix: str) -> None:
    np.savez_compressed(
        path_prefix + ".npz",
        rules=packed.rules,
        rules6=packed.rules6,
        deny_key=packed.deny_key,
        n_rules=np.int64(packed.n_rules),
        n_acls=np.int64(packed.n_acls),
    )
    meta = {
        "key_meta": [dataclasses.asdict(m) for m in packed.key_meta],
        "acl_gid": [[fw, acl, gid] for (fw, acl), gid in packed.acl_gid.items()],
        "bindings": [[fw, iface, gid] for (fw, iface), gid in packed.bindings.items()],
        "bindings_out": [
            [fw, iface, gid] for (fw, iface), gid in packed.bindings_out.items()
        ],
        "parse_skips": [[fw, lineno, reason] for fw, lineno, reason in packed.parse_skips],
    }
    with open(path_prefix + ".json", "w", encoding="utf-8") as f:
        json.dump(meta, f)


#: (lo, hi, name) column pairs that every rule row must keep ordered.  The
#: device predicate is the branch-free wraparound check (x - lo) <= (hi - lo)
#: on uint32, which assumes lo <= hi: an inverted pair would silently match
#: almost every value instead of matching nothing.
_RANGE_COLS = (
    (R_PLO, R_PHI, "proto"),
    (R_SLO, R_SHI, "src"),
    (R_SPLO, R_SPHI, "sport"),
    (R_DLO, R_DHI, "dst"),
    (R_DPLO, R_DPHI, "dport"),
)


def validate_rule_ranges(rules: np.ndarray) -> None:
    """Reject rule rows with inverted lo/hi ranges.

    The parser refuses inverted ranges at parse time (aclparse), but a
    packed artifact saved by an older build may still carry one; under the
    wraparound predicate it would inflate that rule's hit count and remove
    it from the unused/deletion-candidate set with no error.  Fail loudly
    instead, naming the first offending row.
    """
    for lo, hi, name in _RANGE_COLS:
        bad = np.nonzero(rules[:, lo] > rules[:, hi])[0]
        if bad.size:
            row = int(bad[0])
            raise AnalysisError(
                f"packed ruleset row {row} has inverted {name} range "
                f"[{int(rules[row, lo])}, {int(rules[row, hi])}]"
                f" ({bad.size} offending row(s) total); the artifact was "
                "likely written by a pre-wraparound-check build — re-pack "
                "it with parse-acls/convert"
            )


def validate_rule6_ranges(rules6: np.ndarray) -> None:
    """Reject v6 rule rows with inverted lo/hi ranges (v4 twin above).

    Scalar columns use the same check; 128-bit address bounds compare
    lexicographically over their big-endian limbs.
    """
    if rules6.shape[0] == 0:
        return
    for lo, hi, name in ((R6_PLO, R6_PHI, "proto"), (R6_SPLO, R6_SPHI, "sport"),
                         (R6_DPLO, R6_DPHI, "dport")):
        bad = np.nonzero(rules6[:, lo] > rules6[:, hi])[0]
        if bad.size:
            raise AnalysisError(
                f"packed v6 ruleset row {int(bad[0])} has inverted {name} "
                f"range ({bad.size} offending row(s) total); re-pack the "
                "artifact with parse-acls/convert"
            )
    for lo0, hi0, name in ((R6_SLO, R6_SHI, "src"), (R6_DLO, R6_DHI, "dst")):
        lo_limbs = rules6[:, lo0:lo0 + 4].astype(np.uint64)
        hi_limbs = rules6[:, hi0:hi0 + 4].astype(np.uint64)
        n = rules6.shape[0]
        lt = np.zeros(n, dtype=bool)
        gt = np.zeros(n, dtype=bool)
        for i in range(4):  # big-endian lexicographic compare
            lt |= ~gt & (lo_limbs[:, i] < hi_limbs[:, i])
            gt |= ~lt & (lo_limbs[:, i] > hi_limbs[:, i])
        bad = np.nonzero(gt)[0]
        if bad.size:
            raise AnalysisError(
                f"packed v6 ruleset row {int(bad[0])} has inverted {name} "
                f"address range ({bad.size} offending row(s) total); re-pack "
                "the artifact with parse-acls/convert"
            )


def load_packed(path_prefix: str) -> PackedRuleset:
    z = np.load(path_prefix + ".npz")
    with open(path_prefix + ".json", "r", encoding="utf-8") as f:
        meta = json.load(f)
    validate_rule_ranges(z["rules"])
    # rules6 absent in pre-v6 artifacts: those are pure-v4 by construction
    rules6 = z["rules6"] if "rules6" in z.files else None
    if rules6 is not None:
        validate_rule6_ranges(rules6)
    return PackedRuleset(
        rules=z["rules"],
        rules6=rules6,
        n_rules=int(z["n_rules"]),
        n_acls=int(z["n_acls"]),
        key_meta=[KeyMeta(**m) for m in meta["key_meta"]],
        acl_gid={(fw, acl): gid for fw, acl, gid in meta["acl_gid"]},
        deny_key=z["deny_key"],
        bindings={(fw, iface): gid for fw, iface, gid in meta["bindings"]},
        bindings_out={
            (fw, iface): gid for fw, iface, gid in meta.get("bindings_out", [])
        },
        parse_skips=[
            (fw, int(lineno), reason)
            for fw, lineno, reason in meta.get("parse_skips", [])
        ],
    )
