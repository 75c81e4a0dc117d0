"""Packing tests: rule matrix layout, key universe, line packing, serialization."""

import numpy as np

from ruleset_analysis_tpu.hostside import aclparse, pack, synth

CFG = """\
hostname fw1
access-list A extended permit tcp any host 10.0.0.5 eq 443
access-list A extended deny ip any any
access-list B extended permit udp any any eq 53
access-group A in interface outside
"""


def packed_fixture():
    rs = aclparse.parse_asa_config(CFG, "fw1")
    return pack.pack_rulesets([rs]), rs


def test_key_universe():
    packed, _ = packed_fixture()
    assert packed.n_rules == 3
    assert packed.n_acls == 2
    assert packed.n_keys == 5
    # deny keys come after rule keys, one per ACL
    gid_a = packed.acl_gid[("fw1", "A")]
    meta = packed.key_meta[int(packed.deny_key[gid_a])]
    assert meta.implicit_deny and meta.acl == "A" and meta.index == 0


def test_rule_rows_in_config_order():
    packed, _ = packed_fixture()
    rules = packed.rules
    real = rules[:, pack.R_ACL] != pack.NO_ACL
    assert int(real.sum()) == 3
    keys = rules[real][:, pack.R_KEY]
    assert list(keys) == sorted(keys)  # config order preserved


def test_padding_rows_never_match():
    rs = aclparse.parse_asa_config(CFG, "fw1")
    packed = pack.pack_rulesets([rs], pad_rules_to=16)
    assert packed.rules.shape == (16, pack.RULE_COLS)
    padding = packed.rules[3:]
    assert (padding[:, pack.R_ACL] == pack.NO_ACL).all()
    # ranges are [0, 0] which cannot contain any port>0, and acl_gid can
    # never equal a real gid
    assert (padding[:, pack.R_PHI] == 0).all()


def test_bindings_resolve_to_gid():
    packed, _ = packed_fixture()
    assert packed.bindings[("fw1", "outside")] == packed.acl_gid[("fw1", "A")]


def test_line_packer():
    packed, _ = packed_fixture()
    lp = pack.LinePacker(packed)
    lines = [
        "Jul 29 07:48:01 fw1 : %ASA-6-106100: access-list A permitted tcp "
        "inside/1.2.3.4(1000) -> outside/10.0.0.5(443) hit-cnt 1 first hit [0x0, 0x0]",
        "garbage line",
        "Jul 29 07:48:01 fw1 : %ASA-6-106100: access-list NOPE permitted tcp "
        "inside/1.2.3.4(1000) -> outside/10.0.0.5(443) hit-cnt 1 first hit [0x0, 0x0]",
    ]
    batch = lp.pack_lines(lines, batch_size=8)
    assert batch.shape == (8, pack.TUPLE_COLS)
    assert batch[0, pack.T_VALID] == 1
    assert batch[0, pack.T_ACL] == packed.acl_gid[("fw1", "A")]
    assert batch[0, pack.T_DPORT] == 443
    assert int(batch[:, pack.T_VALID].sum()) == 1
    assert lp.skipped == 2


def test_save_load_roundtrip(tmp_path):
    packed, _ = packed_fixture()
    prefix = str(tmp_path / "rules")
    pack.save_packed(packed, prefix)
    loaded = pack.load_packed(prefix)
    np.testing.assert_array_equal(loaded.rules, packed.rules)
    assert loaded.n_rules == packed.n_rules
    assert loaded.acl_gid == packed.acl_gid
    assert loaded.bindings == packed.bindings
    assert loaded.key_meta[0].acl == packed.key_meta[0].acl


def test_multi_firewall_pack():
    rs1 = aclparse.parse_asa_config(CFG, "fw1")
    rs2 = aclparse.parse_asa_config(
        "access-list Z extended permit ip any any\n", "fw2"
    )
    packed = pack.pack_rulesets([rs1, rs2])
    assert packed.n_acls == 3
    assert ("fw2", "Z") in packed.acl_gid
    # keys remain globally unique across firewalls
    assert packed.n_keys == packed.n_rules + packed.n_acls


def test_synth_config_parses_and_packs():
    text = synth.synth_config(n_acls=2, rules_per_acl=8, seed=3)
    rs = aclparse.parse_asa_config(text, "fw1")
    assert rs.rule_count() == 16
    packed = pack.pack_rulesets([rs])
    assert packed.n_rules == 16
    tuples = synth.synth_tuples(packed, 100, seed=3)
    assert tuples.shape == (100, pack.TUPLE_COLS)
    assert tuples[:, pack.T_VALID].all()


def test_pack_overflow_is_clean_error():
    import pytest

    packed, _ = packed_fixture()
    lp = pack.LinePacker(packed)
    line = (
        "Jul 29 07:48:01 fw1 : %ASA-6-106100: access-list A permitted tcp "
        "inside/1.2.3.4(1000) -> outside/10.0.0.5(443) hit-cnt 1 first hit [0x0, 0x0]"
    )
    with pytest.raises(ValueError, match="batch_size"):
        lp.pack_lines([line] * 8, batch_size=4)


def test_load_packed_rejects_inverted_range(tmp_path):
    """A stale artifact with an inverted lo/hi range must fail loudly.

    Under the wraparound predicate (x - lo) <= (hi - lo) an inverted
    range matches nearly everything, silently inflating that rule's hits
    and hiding it from the unused set — so load
    refuses the matrix instead of shipping it.
    """
    import pytest

    from ruleset_analysis_tpu.errors import AnalysisError

    packed, _ = packed_fixture()
    prefix = str(tmp_path / "stale")
    # simulate a pre-wraparound-check build: dport range [443, 80]
    packed.rules[0, pack.R_DPLO] = 443
    packed.rules[0, pack.R_DPHI] = 80
    pack.save_packed(packed, prefix)
    with pytest.raises(AnalysisError, match=r"row 0.*inverted dport|inverted dport.*row 0"):
        pack.load_packed(prefix)


def test_validate_rule_ranges_accepts_padding_and_full_ranges():
    rs = aclparse.parse_asa_config(CFG, "fw1")
    packed = pack.pack_rulesets([rs], pad_rules_to=32)
    pack.validate_rule_ranges(packed.rules)  # must not raise
