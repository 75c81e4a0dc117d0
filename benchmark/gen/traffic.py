"""Flows and syslog text drawn from a ruleset, as ClassBench's trace generator.

Each header is drawn against a filter: a filter picked uniformly, a point
picked uniformly inside it.  A ``miss_fraction`` of
headers are uniform random tuples, which nearly all land in implicit deny.
Each header repeats ``1 + floor(b * U**(-1/a))`` times in a row (ClassBench's
Pareto locality, ``a`` and ``b`` from the traffic file), capped.

Headers are plain arrays: ``acl, proto, src, sport, dst, dport`` (uint32).
ICMP carries its type in ``dport`` with ``sport`` 0, the program's own
convention for the tuple it parses out of a line.
"""

from __future__ import annotations

import numpy as np

from .rules import PORT_MAX, Ruleset, _u32_to_ip

FIELDS = ("acl", "proto", "src", "sport", "dst", "dport")
#: protocols of a header under a wildcard protocol, and of a random miss
_ANY_PROTOS = np.array([6, 17, 1, 50], dtype=np.int64)


def _ports_for(rng, proto: np.ndarray, lo: np.ndarray, hi: np.ndarray, is_s: bool):
    v = rng.integers(lo, hi + 1)
    icmp = proto == 1
    if is_s:
        v = np.where(icmp, 0, v)
    else:
        v = np.where(icmp, rng.integers(0, 16, size=v.shape), v)
    other = (proto != 6) & (proto != 17) & ~icmp
    return np.where(other, 0, v)


def draw_headers(rs: Ruleset, rows: np.ndarray, n: int, rng, miss_fraction: float) -> dict:
    """``n`` headers as a dict of uint32 arrays (see module doc), with
    ``miss`` 1 where the header is a uniform random tuple.

    ``rows`` is :func:`rules.expand` of ``rs``, one row per filter: a filter
    is picked uniformly and a point uniformly inside it.
    """
    r = rows[rng.integers(0, rows.shape[0], size=n)]
    any_proto = r[:, 1] != r[:, 2]
    proto = np.where(any_proto, rng.choice(_ANY_PROTOS, size=n), r[:, 1])
    out = {
        "acl": r[:, 0].copy(),
        "proto": proto,
        "src": rng.integers(r[:, 3], r[:, 4] + 1),
        "sport": _ports_for(rng, proto, r[:, 5], r[:, 6], True),
        "dst": rng.integers(r[:, 7], r[:, 8] + 1),
        "dport": _ports_for(rng, proto, r[:, 9], r[:, 10], False),
    }
    miss = rng.random(n) < miss_fraction
    m = int(miss.sum())
    if m:
        mp = rng.choice(_ANY_PROTOS, size=m)
        out["acl"][miss] = rng.integers(0, len(rs.acls), size=m)
        out["proto"][miss] = mp
        out["src"][miss] = rng.integers(0, 1 << 32, size=m)
        out["dst"][miss] = rng.integers(0, 1 << 32, size=m)
        zeros = np.zeros(m, dtype=np.int64)
        out["sport"][miss] = _ports_for(rng, mp, zeros, zeros + PORT_MAX, True)
        out["dport"][miss] = _ports_for(rng, mp, zeros, zeros + PORT_MAX, False)
    out["miss"] = miss
    return {k: v.astype(np.uint32) for k, v in out.items()}


def burst_sequence(n_lines: int, rng, a: float, b: float, cap: int) -> np.ndarray:
    """Header index of each of ``n_lines`` lines: header h repeated c_h times."""
    reps = []
    total = 0
    while total < n_lines:
        u = 1.0 - rng.random(max(1024, n_lines // 2))
        c = np.minimum(1 + np.floor(b * u ** (-1.0 / a)), cap).astype(np.int64)
        reps.append(c)
        total += int(c.sum())
    c = np.concatenate(reps)
    c = c[: int(np.searchsorted(np.cumsum(c), n_lines)) + 1]
    seq = np.repeat(np.arange(c.size), c)[:n_lines]
    return seq


def make_flows(rs: Ruleset, rows: np.ndarray, traffic: dict, seed: int,
               n_lines: int) -> tuple[dict, np.ndarray]:
    """(headers, seq): the distinct headers and the header index of each line."""
    rng = np.random.default_rng([seed & 0xFFFFFFFFFFFFFFFF, 0xF10E])
    loc = traffic["locality"]
    seq = burst_sequence(n_lines, rng, loc["pareto_a"], loc["pareto_b"], loc["burst_cap"])
    heads = draw_headers(rs, rows, int(seq[-1]) + 1, rng, traffic["miss_fraction"])
    return heads, seq


_PNAME = {6: "tcp", 17: "udp", 1: "icmp"}


def render_lines(rs: Ruleset, heads: dict, stamp: str = "Jul 29 07:48:01") -> list[str]:
    """One ``%ASA-6-106100`` line per header (Cisco's documented format):
    ``permitted`` for a header drawn from a filter (every ACE permits),
    ``denied`` for a random miss."""
    fw = rs.firewall
    out = []
    cols = [heads[k].tolist() for k in FIELDS + ("miss",)]
    for acl, proto, src, sport, dst, dport, miss in zip(*cols):
        a, b = (dport, 0) if proto == 1 else (sport, dport)
        out.append(
            f"{stamp} {fw} : %ASA-6-106100: access-list {rs.acls[acl]} "
            f"{'denied' if miss else 'permitted'} {_PNAME.get(proto, str(proto))} "
            f"{rs.interfaces[acl]}/{_u32_to_ip(src)}({a}) -> inside/{_u32_to_ip(dst)}({b}) "
            f"hit-cnt 1 first hit [0x0, 0x0]"
        )
    return out
