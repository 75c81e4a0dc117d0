"""ASA rulesets of ClassBench-format filters (Taylor & Turner, INFOCOM 2005).

A ClassBench filter set is one ordered classifier of flat 5-tuple filters:
a source and a destination prefix, a source and a destination port range
of one of five classes (WC wildcard, HI 1024-65535, LO 0-1023, AR an
arbitrary range, EM one port), and a protocol, exact or wildcard.  Here
each filter is one ACE of the configuration's one interface ACL, so the
ACL's rows are exactly its filters: no object groups, no expansion.

ClassBench's seed statistics are not in the repo, so every field is drawn
uniformly over the classes ClassBench defines (the configuration's
``assumed``); no share here claims to be a published one.

A ruleset is part of the deployment, like a model's weights, and is drawn
from the configuration's own ``ruleset_seed``: every run of a cell serves
the same firewall, and ``--seed`` draws only the traffic.  The program bakes
the rule tensor into its compiled step, so a ruleset per ``--seed`` would
make every run compile afresh (PERF.md).

The result is kept twice: as plain data (:class:`Ace` lists, which the
reference reads) and rendered as ASA configuration text (which the program
parses with its normal ``parse-acls`` path).  Nothing here imports the
program.
"""

from __future__ import annotations

import dataclasses

import numpy as np

PROTO_NUM = {"tcp": 6, "udp": 17, "icmp": 1, "ip": None}
PORT_MAX = 65535


@dataclasses.dataclass(frozen=True)
class Ace:
    """One ACE: inclusive (lo, hi) ranges; ``proto`` None for ``ip`` (any)."""

    acl: int
    index: int  # 1-based position in its ACL, as ASA numbers lines
    permit: bool
    proto: int | None
    src: tuple
    sport: tuple
    dst: tuple
    dport: tuple
    text: str


@dataclasses.dataclass
class Ruleset:
    firewall: str
    acls: list  # ACL names, index = acl id
    interfaces: list  # ingress interface bound to each ACL
    aces: list  # list[list[Ace]], per ACL in configuration order
    text: str

    @property
    def n_aces(self) -> int:
        return sum(len(a) for a in self.aces)

    @property
    def n_rows(self) -> int:
        return self.n_aces


def _u32_to_ip(v: int) -> str:
    return f"{v >> 24 & 255}.{v >> 16 & 255}.{v >> 8 & 255}.{v & 255}"


def _mask(plen: int) -> int:
    return (0xFFFFFFFF << (32 - plen)) & 0xFFFFFFFF if plen else 0


def _prefix(rng, lens: list) -> tuple[tuple[int, int], str]:
    plen = int(lens[int(rng.integers(0, len(lens)))])
    lo = int(rng.integers(0, 1 << 32)) & _mask(plen)
    hi = lo | (~_mask(plen) & 0xFFFFFFFF)
    if plen == 0:
        return (lo, hi), "any"
    if plen == 32:
        return (lo, hi), f"host {_u32_to_ip(lo)}"
    return (lo, hi), f"{_u32_to_ip(lo)} {_u32_to_ip(_mask(plen))}"


def _ports(rng, classes: list) -> tuple[tuple[int, int], str]:
    """One port range of a ClassBench port class, and its ASA operator."""
    cls = classes[int(rng.integers(0, len(classes)))]
    if cls == "WC":
        return (0, PORT_MAX), ""
    if cls == "HI":
        return (1024, PORT_MAX), " gt 1023"
    if cls == "LO":
        return (0, 1023), " lt 1024"
    lo = int(rng.integers(0, PORT_MAX + 1))
    if cls == "EM":
        return (lo, lo), f" eq {lo}"
    hi = int(rng.integers(lo, PORT_MAX + 1))
    return (lo, hi), (f" eq {lo}" if hi == lo else f" range {lo} {hi}")


def make_ruleset(cfg: dict) -> Ruleset:
    """Draw the ruleset of configuration ``cfg`` (its JSON)."""
    a = cfg["assumed"]
    rng = np.random.default_rng(cfg["ruleset_seed"])
    acl, fw = cfg["acl"], cfg["firewall"]
    text = [f"hostname {fw}", "!"]
    aces = []
    for i in range(cfg["filters"]):
        pname = a["protocol"][int(rng.integers(0, len(a["protocol"])))]
        src, src_t = _prefix(rng, a["prefix_len"])
        dst, dst_t = _prefix(rng, a["prefix_len"])
        sport = dport = (0, PORT_MAX)
        sport_t = dport_t = ""
        if pname in ("tcp", "udp"):
            sport, sport_t = _ports(rng, a["port_class"])
            dport, dport_t = _ports(rng, a["port_class"])
        line = f"access-list {acl} extended permit {pname} {src_t}{sport_t} {dst_t}{dport_t}"
        text.append(line)
        aces.append(Ace(0, i + 1, True, PROTO_NUM[pname], src, sport, dst, dport, line))
    text.append(f"access-group {acl} in interface {cfg['interface']}")
    return Ruleset(fw, [acl], [cfg["interface"]], [aces], "\n".join(text) + "\n")


#: columns of :func:`expand`'s rows
ROW_COLS = ("acl", "proto_lo", "proto_hi", "src_lo", "src_hi", "sport_lo",
            "sport_hi", "dst_lo", "dst_hi", "dport_lo", "dport_hi", "ace")


def expand(rs: Ruleset) -> np.ndarray:
    """One row per ACE: ``[rows, 12]`` int64 in configuration order, the
    last column the ACE's position in ``[ace for acl in rs.aces for ace in acl]``."""
    out = []
    for k, ace in enumerate(a for acl in rs.aces for a in acl):
        plo, phi = (0, 255) if ace.proto is None else (ace.proto, ace.proto)
        out.append((ace.acl, plo, phi, *ace.src, *ace.sport, *ace.dst, *ace.dport, k))
    return np.asarray(out, dtype=np.int64).reshape(-1, len(ROW_COLS))
