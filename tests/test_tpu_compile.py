"""Ahead-of-time compiles of the main path for a described v5e chip.

The TPU compiler is installed here and compiles for a chip that is
described, not attached (on-chip guide §2): what it refuses (a program
that does not fit, a kernel slice off the tiling) costs no chip time.
Nothing runs, so these say nothing about results or speed.

Each step program is reached through the devprof dispatch seam: the step
builders return Python closures whose jits dispatch through
``devprof.active_capture()``, and the smoke's recorder standing there
keeps the jit instead of running it.  The topology is described inside a
fixture, never at import, so every xdist worker collects the same tests
and only the one given this file loads the TPU library.
"""

import os

import numpy as np
import pytest

import chip_smoke

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from ruleset_analysis_tpu.config import AnalysisConfig  # noqa: E402
from ruleset_analysis_tpu.hostside import aclparse, pack, synth  # noqa: E402
from ruleset_analysis_tpu.models import pipeline  # noqa: E402
from ruleset_analysis_tpu.parallel import mesh as mesh_lib  # noqa: E402
from ruleset_analysis_tpu.parallel import step as step_lib  # noqa: E402

LINES = 1 << 20  # per chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe the chip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def ruleset():
    """The smoke's enterprise ASA: 8 ACLs x 512 rules, v4 and v6 rows."""
    rs = aclparse.parse_asa_config(
        synth.synth_config(n_acls=8, rules_per_acl=512, seed=0, v6_fraction=0.1),
        "fw1",
    )
    return pack.pack_rulesets([rs])


@pytest.fixture
def tpu_backend(monkeypatch):
    """Trace as the chip does: code that asks for the backend sees a TPU
    (here it sees the CPU, and would take the CPU's XLA scan)."""
    from ruleset_analysis_tpu.ops import match as match_ops

    monkeypatch.setattr(match_ops, "tpu_backend", lambda: True)
    step_lib._cached_step.cache_clear()
    yield
    step_lib._cached_step.cache_clear()


def _compile(mesh, make_step, ruleset, rules, batch_shape, batch_spec, label,
             match_impl="xla"):
    """Dispatch one step through the seam and compile its jit for ``mesh``."""
    cfg = AnalysisConfig(batch_size=batch_shape[-1], match_impl=match_impl)
    rep = NamedSharding(mesh, P())
    state = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=rep),
        pipeline.init_state_host(ruleset.n_keys, cfg),
    )
    batch = jax.ShapeDtypeStruct(
        batch_shape, jnp.uint32, sharding=NamedSharding(mesh, batch_spec)
    )
    with chip_smoke.DispatchRecorder(run=False).installed() as rec:
        make_step(mesh, cfg, ruleset.n_keys)(state, rules, batch, 0)
    return rec.lower(label).compile()


def _flat(topo, ruleset, n_chips, match_impl="xla"):
    mesh = mesh_lib.make_mesh(topo.devices[:n_chips])
    wire_cols = pack.compact_batch(np.zeros((pack.TUPLE_COLS, 8), np.uint32)).shape[0]
    return _compile(
        mesh, step_lib.make_parallel_step, ruleset, pipeline.ship_ruleset(ruleset),
        (wire_cols, LINES * n_chips), P(None, "data"), "step.flat", match_impl,
    )


def test_flat_step_compiles_one_chip(topo, ruleset, tpu_backend):
    compiled = _flat(topo, ruleset, 1)
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30
    # the TPU's flat step runs the lane-dense scan
    assert "tpu_custom_call" in compiled.as_text()


def test_flat_step_compiles_four_chips_with_all_reduce(topo, ruleset, tpu_backend):
    compiled = _flat(topo, ruleset, 4)
    text = compiled.as_text()
    assert "all-reduce" in text
    assert "tpu_custom_call" in text


BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "benchmark")


def _bench_module(name):
    """A module of ``benchmark/`` (a directory of scripts, not a package)."""
    import importlib
    import sys

    if BENCH not in sys.path:
        sys.path.append(BENCH)
    return importlib.import_module(name)


def test_flat_step_kernel_keeps_the_match_scope(topo, ruleset, tpu_backend):
    """With the kernel forced, the compiled flat step's first-match ops
    (the kernel's custom call) carry ``ra.match`` in their op metadata:
    the stage table the benchmark's ``match_roofline`` reads its time by
    (``benchmark/hlo_scopes.py``) would otherwise find no match time."""
    import re

    text = _flat(topo, ruleset, 1, match_impl="pallas").as_text()
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert calls
    for line in calls:
        assert re.search(r'op_name="[^"]*/ra\.match/', line), line
    _, table = _bench_module("hlo_scopes").scopes_of_module(text)
    names = {re.match(r"\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=", line).group(1) for line in calls}
    assert {table.get(n) for n in names} == {"match"}


def test_v6_step_compiles(topo, ruleset):
    assert ruleset.has_v6
    mesh = mesh_lib.make_mesh(topo.devices[:1])
    compiled = _compile(
        mesh, step_lib.make_parallel_step6, ruleset,
        pipeline.ship_ruleset6(ruleset), (pack.TUPLE6_COLS, LINES),
        P(None, "data"), "step.v6",
    )
    assert compiled.memory_analysis() is not None


@pytest.fixture(scope="module")
def dual_ruleset():
    """The ``asa-dual-10k`` cell's unified ACL (10,000 ACEs, about 4,500
    IPv6), drawn by the benchmark's generator and packed by the program."""
    import json

    rules_dual = _bench_module("gen.rules_dual")
    with open(os.path.join(BENCH, "configs", "asa-dual-10k.json"), encoding="utf-8") as f:
        rs = rules_dual.make_ruleset(json.load(f))
    return pack.pack_rulesets([aclparse.parse_asa_config(rs.text, rs.firewall)])


def test_v6_step_compiles_at_the_dual_cell_size(topo, dual_ruleset):
    assert 4000 < dual_ruleset.rules6.shape[0] < 5000
    mesh = mesh_lib.make_mesh(topo.devices[:1])
    compiled = _compile(
        mesh, step_lib.make_parallel_step6, dual_ruleset,
        pipeline.ship_ruleset6(dual_ruleset), (pack.TUPLE6_COLS, LINES),
        P(None, "data"), "step.v6",
    )
    assert compiled.memory_analysis() is not None


def test_stacked_step_compiles(topo, ruleset):
    mesh = mesh_lib.make_mesh(topo.devices[:1])
    lane = LINES // ruleset.n_acls
    grouped = pack.group_tuples(
        np.zeros((8, pack.TUPLE_COLS), np.uint32), ruleset.n_acls, lane=8
    )
    wire_cols = pack.compact_grouped(grouped).shape[1]
    compiled = _compile(
        mesh, step_lib.make_parallel_step_stacked, ruleset,
        pipeline.ship_ruleset_stacked(ruleset),
        (ruleset.n_acls, wire_cols, lane), P(None, None, "data"), "step.stacked",
    )
    assert compiled.memory_analysis() is not None


def test_pallas_match_kernel_compiles(topo):
    from jax.sharding import SingleDeviceSharding

    from ruleset_analysis_tpu.ops import pallas_match

    one_chip = SingleDeviceSharding(topo.devices[0])
    rows = 10240  # asa-10k's rule tensor: 10,000 rows padded to 20 rule blocks
    cols = {
        k: jax.ShapeDtypeStruct((LINES,), jnp.uint32, sharding=one_chip)
        for k in ("acl", "proto", "src", "sport", "dst", "dport")
    }
    rules = jax.ShapeDtypeStruct((rows, pack.RULE_COLS), jnp.uint32, sharding=one_chip)
    compiled = jax.jit(
        pallas_match.first_match_rows_pallas, static_argnames="interpret"
    ).lower(cols, rules, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
