"""The plain reference: what a report over the generated lines must say.

It reads the generator's plain data (the ACEs, and the headers behind
every line) and never the program: no parse, no packed tables.  First
match is a per-ACL scan of the ACL's rows in configuration order, in
``jax.numpy`` on the default device (the run has freed the program's state
before this runs).  The sketches are recomputed in numpy from their stated
definitions (the configuration's ``guarantees``), so a report can be held
to them exactly; the talkers are held to the exact lines of each source.
"""

from __future__ import annotations

import ipaddress

import numpy as np

from gen.rules import Ruleset

#: talkers a report shows per ACL (the ``topk`` of the program's ``run``)
TALKERS_SHOWN = 10
_B = 8192  # headers per device block
_C = 2048  # rows per device block
_NONE = np.int64(np.iinfo(np.int64).max)


def _chunk_first():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def first(t, r):
        # t: [B, 5] proto, src, sport, dst, dport; r: [C, 10] lo/hi pairs
        ok = jnp.ones((t.shape[0], r.shape[0]), dtype=bool)
        for f in range(5):
            x = t[:, f][:, None]
            ok &= (x >= r[:, 2 * f][None, :]) & (x <= r[:, 2 * f + 1][None, :])
        idx = jnp.where(ok, jnp.arange(r.shape[0])[None, :], r.shape[0])
        return idx.min(axis=1)

    return first


def first_match(rows: np.ndarray, heads: dict, n_acls: int) -> np.ndarray:
    """Flat ACE index each header's first match lands on, -1 for implicit deny."""
    import jax.numpy as jnp

    first = _chunk_first()
    n = heads["acl"].size
    out = np.full(n, -1, dtype=np.int64)
    t_all = np.stack([heads[k] for k in ("proto", "src", "sport", "dst", "dport")], 1)
    for acl in range(n_acls):
        hi = np.flatnonzero(heads["acl"] == acl)
        r_acl = rows[rows[:, 0] == acl]
        if hi.size == 0 or r_acl.shape[0] == 0:
            continue
        best = np.full(hi.size, _NONE, dtype=np.int64)
        for c0 in range(0, r_acl.shape[0], _C):
            rc = r_acl[c0:c0 + _C]
            pad = np.zeros((_C, 10), dtype=np.uint32)
            pad[:, 0::2] = 1  # empty ranges (lo 1 > hi 0) never match
            pad[: rc.shape[0]] = rc[:, 1:11].astype(np.uint32)
            rd = jnp.asarray(pad)
            for b0 in range(0, hi.size, _B):
                sel = hi[b0:b0 + _B]
                tb = np.zeros((_B, 5), dtype=np.uint32)
                tb[: sel.size] = t_all[sel]
                got = np.asarray(first(jnp.asarray(tb), rd))[: sel.size].astype(np.int64)
                cand = np.where(got < _C, c0 + got, _NONE)
                best[b0:b0 + _B] = np.minimum(best[b0:b0 + _B], cand)
        found = best != _NONE
        out[hi[found]] = r_acl[best[found], 11]
    return out


# -- sketches, from the configuration's stated definitions -------------------

_M32 = np.uint32(0xFFFFFFFF)


def fmix32(x: np.ndarray, seed: int = 0) -> np.ndarray:
    x = x.astype(np.uint32) ^ np.uint32(seed)
    x = x ^ (x >> np.uint32(16))
    x = x * np.uint32(0x85EBCA6B)
    x = x ^ (x >> np.uint32(13))
    x = x * np.uint32(0xC2B2AE35)
    return x ^ (x >> np.uint32(16))


def _clz32(x: np.ndarray) -> np.ndarray:
    x = x.astype(np.int64)
    n = np.full(x.shape, 32, dtype=np.int64)
    for shift in (16, 8, 4, 2, 1):
        big = x >= (1 << shift)
        n = np.where(big, n - shift, n)
        x = np.where(big, x >> shift, x)
    return n - x


def hll_estimates(keys: np.ndarray, src: np.ndarray, n_keys: int, p: int) -> np.ndarray:
    """Per-key HLL estimate over the (key, src) pairs given (duplicates fine)."""
    m = 1 << p
    reg = (fmix32(src, 0xB5297A4D) >> np.uint32(32 - p)).astype(np.int64)
    rank = _clz32(fmix32(src, 0x68E31DA4)) + 1
    regs = np.zeros((n_keys, m), dtype=np.int64)
    np.maximum.at(regs, (keys, reg), rank)
    r = regs.astype(np.float64)
    alpha = {16: 0.673, 32: 0.697, 64: 0.709}.get(m, 0.7213 / (1 + 1.079 / m))
    raw = alpha * m * m / np.sum(np.exp2(-r), axis=1)
    zeros = np.sum(r == 0, axis=1)
    small = (raw <= 2.5 * m) & (zeros > 0)
    with np.errstate(divide="ignore"):
        linear = m * np.log(m / np.maximum(zeros, 1e-12))
    return np.minimum(np.where(small, linear, raw), 2.0 ** 32)


_MS = np.array([0x9E3779B1, 0x85EBCA77, 0xC2B2AE3D, 0x27D4EB2F,
                0x165667B1, 0x9E3779B9, 0x85EBCA6B, 0xC2B2AE35], dtype=np.uint32)


def pair_code(acl: np.ndarray, src: np.ndarray) -> np.ndarray:
    h = fmix32(acl)
    return fmix32(h ^ src.astype(np.uint32) * np.uint32(0x9E3779B1), 0x51ED)


def cms_rows(codes: np.ndarray, width: int, depth: int) -> np.ndarray:
    bits = width.bit_length() - 1
    mixed = fmix32(codes)
    return (mixed[None, :] * _MS[:depth, None]) >> np.uint32(32 - bits)


def talker_cms(acl: np.ndarray, src: np.ndarray, weight: np.ndarray,
               width: int, depth: int) -> np.ndarray:
    cms = np.zeros((depth, width), dtype=np.int64)
    b = cms_rows(pair_code(acl, src), width, depth)
    for d in range(depth):
        np.add.at(cms[d], b[d].astype(np.int64), weight)
    return cms


def talker_estimate(cms: np.ndarray, acl: np.ndarray, src: np.ndarray) -> np.ndarray:
    b = cms_rows(pair_code(acl, src), cms.shape[1], cms.shape[0]).astype(np.int64)
    return np.min(np.stack([cms[d, b[d]] for d in range(cms.shape[0])]), axis=0)


# -- the expected report -----------------------------------------------------


class Expected:
    """What a report over ``mult[h]`` copies of each header ``h`` must hold."""

    def __init__(self, rs: Ruleset, heads: dict, ace_of: np.ndarray,
                 mult: np.ndarray, sketch: dict):
        flat = [ace for acl in rs.aces for ace in acl]
        n_rules = len(flat)
        n_acls = len(rs.acls)
        # rule keys: ACEs in order, then each ACL's implicit deny
        key = np.where(ace_of >= 0, ace_of, n_rules + heads["acl"].astype(np.int64))
        live = mult > 0
        self.lines = int(mult.sum())
        counts = np.bincount(key, weights=mult, minlength=n_rules + n_acls)
        self.names = [(rs.firewall, rs.acls[a.acl], a.index) for a in flat]
        self.names += [(rs.firewall, n, 0) for n in rs.acls]  # implicit deny
        self.hits = {self.names[k]: int(round(counts[k])) for k in range(len(counts))}
        self.unused = sorted(
            (rs.firewall, rs.acls[a.acl], a.index) for i, a in enumerate(flat)
            if counts[i] == 0
        )
        card = hll_estimates(key[live], heads["src"][live], n_rules + n_acls,
                             sketch["hll_p"])
        self.unique = {self.names[k]: int(round(card[k]))
                       for k in range(len(counts)) if counts[k] > 0}
        # the program's ACL ids follow the ACLs' order of first appearance
        # in the configuration text, which is rs.acls order by construction
        self.talk = talker_cms(heads["acl"][live], heads["src"][live], mult[live],
                               sketch["cms_width"], sketch["talk_cms_depth"])
        self.acl_index = {f"{rs.firewall} {n}": i for i, n in enumerate(rs.acls)}
        self.seen = {(int(a), int(s)) for a, s in zip(heads["acl"][live], heads["src"][live])}
        # exact lines per (acl, src)
        pair = heads["acl"][live].astype(np.int64) << 32 | heads["src"][live].astype(np.int64)
        uniq, inv = np.unique(pair, return_inverse=True)
        self.pair_acl = uniq >> 32
        self.pair_src = uniq & 0xFFFFFFFF
        self.pair_lines = np.bincount(inv, weights=mult[live]).astype(np.int64)


def _ip(s: str) -> int:
    return int(ipaddress.IPv4Address(s))


def compare(report: dict, exp: Expected) -> dict:
    """Disagreements between a report and the reference, by kind (all 0 when sound)."""
    got = {(e["firewall"], e["acl"], e["index"]): e for e in report["per_rule"]}
    hits_wrong = 0
    uniq_wrong = 0
    for name, want in exp.hits.items():
        e = got.get(name)
        if e is None:
            hits_wrong += want != 0
            continue
        hits_wrong += int(e["hits"]) != want
        if want > 0:
            uniq_wrong += e.get("unique_sources") != exp.unique.get(name)
    hits_wrong += int(report["totals"]["lines_total"] != exp.lines)
    unused_wrong = len({tuple(u) for u in report["unused"]} ^ set(exp.unused))
    talker_wrong = 0
    talkers = report.get("talkers") or {}
    for acl_name, items in talkers.items():
        a = exp.acl_index.get(acl_name)
        if a is None:
            talker_wrong += len(items)
            continue
        src = np.array([_ip(s) for s, _ in items], dtype=np.uint32)
        est = np.array([c for _, c in items], dtype=np.int64)
        bound = talker_estimate(exp.talk, np.full(src.size, a, dtype=np.uint32), src)
        for s, c, b in zip(src.tolist(), est.tolist(), bound.tolist()):
            talker_wrong += (a, s) not in exp.seen or c > b or c < 1
    return {"hits_wrong": hits_wrong, "unused_wrong": unused_wrong,
            "unique_wrong": uniq_wrong, "talkers_wrong": talker_wrong,
            "talkers_missed": talkers_missed(talkers, exp)}


def talkers_missed(talkers: dict, exp: Expected) -> int:
    """Each ACL's true heaviest sources (its ``TALKERS_SHOWN`` largest exact
    counts) that its reported talkers leave out while the list holds a
    source with under half their exact count (or is not full).

    The program ranks talkers by the sketch estimate it last saw for each,
    which can lag a source's count by the lines since it was last a chunk
    candidate, so near-ties may swap; half is far outside that lag, and a
    list of wrong sources, or none, reads high."""
    missed = 0
    for name, a in exp.acl_index.items():
        mine = exp.pair_acl == a
        if not mine.any():
            continue
        lines, src = exp.pair_lines[mine], exp.pair_src[mine]
        exact = dict(zip(src.tolist(), lines.tolist()))
        top = np.argsort(-lines, kind="stable")[:TALKERS_SHOWN]
        shown = {_ip(s) for s, _ in talkers.get(name, [])}
        floor = min(exact.get(s, 0) for s in shown) if len(shown) >= TALKERS_SHOWN else 0
        for s, c in zip(src[top].tolist(), lines[top].tolist()):
            missed += s not in shown and 2 * floor < c
    return missed
