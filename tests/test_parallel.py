"""Multi-device tests on the fake 8-device CPU mesh (SURVEY.md §5).

The decisive property: psum/pmax-merged registers from a sharded run are
BIT-IDENTICAL to the single-device run over the same concatenated batch —
integer adds/maxes are exactly associative and commutative.  This is the
rebuild's substitute for the reference's (nonexistent) distributed tests
and the correctness basis for multi-chip scale-out and resume-by-merge.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp

from ruleset_analysis_tpu.config import AnalysisConfig, SketchConfig
from ruleset_analysis_tpu.hostside import aclparse, oracle, pack, synth
from ruleset_analysis_tpu.models import pipeline
from ruleset_analysis_tpu.parallel import mesh as mesh_lib
from ruleset_analysis_tpu.parallel.step import make_parallel_step


def cpu_devices():
    """8 fake CPU devices (conftest's --xla_force_host_platform_device_count)."""
    devs = jax.devices()
    assert len(devs) == 8, "conftest must provide 8 fake CPU devices"
    return devs


@pytest.fixture(scope="module")
def setup():
    cfg_text = synth.synth_config(n_acls=3, rules_per_acl=12, seed=31)
    rs = aclparse.parse_asa_config(cfg_text, "fw1")
    packed = pack.pack_rulesets([rs])
    cfg = AnalysisConfig(
        batch_size=1024, sketch=SketchConfig(cms_width=1 << 10, cms_depth=4, hll_p=6)
    )
    batch_np = np.ascontiguousarray(synth.synth_tuples(packed, 1024, seed=31).T)
    return packed, rs, cfg, batch_np


def run_on_mesh(packed, cfg, batch_np, devices):
    mesh = mesh_lib.make_mesh(devices)
    step = make_parallel_step(mesh, cfg, packed.n_keys)
    state = pipeline.init_state(packed.n_keys, cfg)
    rules = pipeline.ship_ruleset(packed)
    batch = mesh_lib.shard_batch(mesh, batch_np)
    state, out = step(state, rules, batch)
    return jax.device_get(state), jax.device_get(out)


def test_eight_device_state_bit_identical_to_single(setup):
    packed, rs, cfg, batch_np = setup
    s8, _ = run_on_mesh(packed, cfg, batch_np, cpu_devices())
    s1, _ = run_on_mesh(packed, cfg, batch_np, cpu_devices()[:1])
    for name in pipeline.AnalysisState._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(s8, name)), np.asarray(getattr(s1, name)), err_msg=name
        )


def test_shard_order_invariance(setup):
    """Permuting lines across shards must not change merged registers."""
    packed, rs, cfg, batch_np = setup
    rng = np.random.default_rng(0)
    perm = rng.permutation(batch_np.shape[1])
    s_a, _ = run_on_mesh(packed, cfg, batch_np, cpu_devices())
    s_b, _ = run_on_mesh(packed, cfg, np.ascontiguousarray(batch_np[:, perm]), cpu_devices())
    for name in ("counts_lo", "counts_hi", "cms", "hll", "talk_cms"):
        np.testing.assert_array_equal(
            np.asarray(getattr(s_a, name)), np.asarray(getattr(s_b, name)), err_msg=name
        )


def test_parallel_counts_match_oracle(setup):
    packed, rs, cfg, batch_np = setup
    s8, _ = run_on_mesh(packed, cfg, batch_np, cpu_devices())
    # oracle over the same tuples (render -> parse round trip)
    lines = synth.render_syslog(packed, np.ascontiguousarray(batch_np.T), seed=31)
    res = oracle.Oracle([rs]).consume(lines)
    from ruleset_analysis_tpu.ops.counts import to_u64

    per_key = to_u64(np.asarray(s8.counts_lo), np.asarray(s8.counts_hi))
    got = {}
    for key_id, meta in enumerate(packed.key_meta):
        if per_key[key_id]:
            got[(meta.firewall, meta.acl, meta.index)] = int(per_key[key_id])
    assert got == dict(res.hits)


def test_candidates_are_replicated_and_cover_all_shards(setup):
    packed, rs, cfg, batch_np = setup
    _, out = run_on_mesh(packed, cfg, batch_np, cpu_devices())
    k = cfg.sketch.topk_chunk_candidates
    assert out.cand_acl.shape == (8 * k,)
    assert out.cand_src.shape == (8 * k,)


def test_run_stream_uses_mesh_and_matches_single(setup):
    """Full driver on the 8-device mesh == oracle (end to end)."""
    from ruleset_analysis_tpu.runtime.stream import run_stream

    packed, rs, cfg, batch_np = setup
    lines = synth.render_syslog(packed, np.ascontiguousarray(batch_np.T), seed=31)
    rep = run_stream(
        packed, iter(lines), cfg, topk=5, mesh=mesh_lib.make_mesh(cpu_devices())
    )
    res = oracle.Oracle([rs]).consume(lines)
    got = {
        (e["firewall"], e["acl"], e["index"]): e["hits"] for e in rep.per_rule if e["hits"]
    }
    assert got == dict(res.hits)
    assert rep.unused == res.unused_rules([rs])


def test_step_specialization_cache_correct_across_rulesets():
    """The ruleset-specialized step cache must dispatch by VALUE: two
    different rulesets through one step object give each its own correct
    counts, and an equal-valued re-shipped ruleset reuses the executable."""
    cfg = AnalysisConfig(batch_size=64, sketch=SketchConfig(cms_width=1 << 10, cms_depth=2, hll_p=4))
    mesh = mesh_lib.make_mesh(axis=cfg.mesh_axis)

    def setup(seed):
        rs = aclparse.parse_asa_config(
            synth.synth_config(n_acls=2, rules_per_acl=6, seed=seed), "fw1"
        )
        packed = pack.pack_rulesets([rs])
        tup = synth.synth_tuples(packed, 64, seed=seed)
        wire = pack.compact_batch(np.ascontiguousarray(tup.T))
        return packed, wire

    pa, wa = setup(1)
    pb, wb = setup(2)
    assert pa.n_keys == pb.n_keys  # same key space, different rule values
    step = make_parallel_step(mesh, cfg, pa.n_keys)

    def run(packed, wire):
        rules = pipeline.ship_ruleset(packed)
        st = pipeline.init_state(packed.n_keys, cfg)
        st, _ = step(st, rules, mesh_lib.shard_batch(mesh, wire, cfg.mesh_axis))
        return np.asarray(st.counts_lo).copy()

    ca1 = run(pa, wa)
    cb = run(pb, wb)
    ca2 = run(pa, wa)  # re-shipped equal-valued ruleset (new object)
    np.testing.assert_array_equal(ca1, ca2)
    assert not np.array_equal(ca1, cb)  # different rules really dispatched
    assert ca1.sum() > 0 and cb.sum() > 0
