"""Elastic recovery: automatic cluster re-formation after peer death.

The acceptance surface of the SURVEY §3b elastic/retry analog
(runtime/elastic.py): 4 launcher processes run `run --distributed
--elastic` over 4 shards; one process is killed mid-run (abrupt
``os._exit`` mid-collective, the injected-fault analog of a node dying).
The survivors must detect the loss, re-form jax.distributed at world
size 3 with a re-elected coordinator, re-split the unread shards, resume
from the shared epoch checkpoint — and the final unused-rule report must
be BIT-IDENTICAL to an uninterrupted run over the same input, with no
manual ``--resume`` invocation anywhere.
"""

import json
import os
import subprocess
import sys

import pytest

from ruleset_analysis_tpu.hostside import aclparse, pack, synth
from ruleset_analysis_tpu.runtime.elastic import assign_shards

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# Pure-host units (no processes, no jax)
# ---------------------------------------------------------------------------


def test_assign_shards_round_robin_complete():
    shards = [f"s{i}" for i in range(5)]
    out = assign_shards(shards, {1: 100}, {0}, 3)
    # every remaining shard assigned exactly once, cursors preserved
    assert out == [
        [(1, "s1", 100), (4, "s4", 0)],
        [(2, "s2", 0)],
        [(3, "s3", 0)],
    ]
    # shrinking world re-splits the same remaining work
    out2 = assign_shards(shards, {1: 100}, {0}, 2)
    flat = sorted(x for part in out2 for x in part)
    assert flat == [(1, "s1", 100), (2, "s2", 0), (3, "s3", 0), (4, "s4", 0)]


def test_assign_shards_more_ranks_than_shards():
    out = assign_shards(["a", "b"], {}, {}, 4)
    assert out == [[(0, "a", 0)], [(1, "b", 0)], [], []]


def test_supervisor_refuses_wire_shards_and_no_cadence(tmp_path):
    from ruleset_analysis_tpu.config import AnalysisConfig
    from ruleset_analysis_tpu.errors import AnalysisError
    from ruleset_analysis_tpu.hostside import wire
    from ruleset_analysis_tpu.runtime.elastic import ElasticSupervisor

    log = tmp_path / "a.log"
    log.write_text("x\n")
    with pytest.raises(AnalysisError, match="checkpoint"):
        ElasticSupervisor(
            str(tmp_path / "d"), 0, 2, "rs", [str(log)],
            AnalysisConfig(checkpoint_every_chunks=0),
        )
    w = tmp_path / "a.rawire"
    w.write_bytes(wire.MAGIC + b"\0" * 64)
    with pytest.raises(AnalysisError, match="rawire"):
        ElasticSupervisor(
            str(tmp_path / "d"), 0, 2, "rs", [str(w)],
            AnalysisConfig(checkpoint_every_chunks=2),
        )


# ---------------------------------------------------------------------------
# Multi-process recovery (the acceptance tests)
# ---------------------------------------------------------------------------


def _launcher_env(n_local_devices: int) -> dict:
    from cpuenv import cpu_env

    return cpu_env(n_local_devices)


@pytest.fixture(scope="module")
def corpus(tmp_path_factory):
    td = tmp_path_factory.mktemp("elastic")
    cfg_text = synth.synth_config(
        n_acls=3, rules_per_acl=8, seed=41, egress_acls=True
    )
    rs = aclparse.parse_asa_config(cfg_text, "fw1")
    packed = pack.pack_rulesets([rs])
    tuples = synth.synth_tuples(packed, 1600, seed=42)
    lines = synth.render_syslog(packed, tuples, seed=43, variety=0.4)
    prefix = str(td / "packed")
    pack.save_packed(packed, prefix)
    shards = []
    for i in range(4):
        p = td / f"shard{i}.log"
        p.write_text(
            "".join(ln + "\n" for ln in lines[i * 400 : (i + 1) * 400]),
            encoding="utf-8",
        )
        shards.append(str(p))
    return td, prefix, shards


def _spawn_launchers(td, prefix, shards, *, fault=None, max_reforms=2,
                     timeout=400):
    env = _launcher_env(2)
    if fault:
        env["RA_ELASTIC_FAULT"] = fault
    eldir = str(td / "eldir")
    procs = [
        subprocess.Popen(
            [sys.executable, "-m", "ruleset_analysis_tpu.cli", "run",
             "--ruleset", prefix, "--logs", *shards, "--backend", "tpu",
             "--distributed", "--elastic", "--elastic-dir", eldir,
             "--num-processes", "4", "--process-id", str(pid),
             "--batch-size", "64", "--checkpoint-every", "2",
             "--max-reforms", str(max_reforms),
             "--json", "--out", str(td / f"rep{pid}.json")]
            ,
            env=env, cwd=_REPO,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        )
        for pid in range(4)
    ]
    outs = []
    for p in procs:
        try:
            out, err = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise AssertionError("elastic launcher HUNG (no bounded-time exit)")
        outs.append((p.returncode, out, err))
    return outs


def _reference_report(prefix, shards):
    from ruleset_analysis_tpu.config import AnalysisConfig
    from ruleset_analysis_tpu.runtime.stream import run_stream_file

    packed = pack.load_packed(prefix)
    rep = run_stream_file(packed, shards, AnalysisConfig(batch_size=64))
    return json.loads(rep.to_json())


def test_kill_one_of_four_auto_reforms_bit_identical(corpus):
    """Kill tag 2 mid-run: survivors re-form at world 3, resume from the
    epoch checkpoint, and the unused-rule report is bit-identical to an
    uninterrupted run — no manual --resume anywhere."""
    td, prefix, shards = corpus
    outs = _spawn_launchers(td, prefix, shards, fault="tag=2,after_batches=4")

    from ruleset_analysis_tpu.runtime.elastic import DIE_RC

    assert outs[2][0] == DIE_RC, (
        f"victim exited rc={outs[2][0]}\nstderr:\n{outs[2][2][-2000:]}"
    )
    for pid in (0, 1, 3):
        rc, _out, err = outs[pid]
        assert rc == 0, f"survivor {pid} failed rc={rc}\nstderr:\n{err[-3000:]}"

    # the re-elected rank 0 (lowest surviving tag) wrote the report
    rep = json.loads((td / "rep0.json").read_text(encoding="utf-8"))
    t = rep["totals"]
    assert t["processes"] == 3  # re-formed at the surviving world size
    assert t["elastic_epoch"] >= 1  # at least one re-formation happened
    # recovery events + time-to-recover surfaced in the report totals
    rec = t["recovery"]
    assert rec["reforms_used"] >= 1
    assert rec["recovery_events"] >= 1
    assert all(e["time_to_recover_sec"] >= 0 for e in rec["recoveries"])

    ref = _reference_report(prefix, shards)
    hits = lambda r: {  # noqa: E731
        (e["firewall"], e["acl"], e["index"]): e["hits"] for e in r["per_rule"]
    }
    assert hits(rep) == hits(ref)
    assert rep["unused"] == ref["unused"]
    assert t["lines_total"] == ref["totals"]["lines_total"] == 1600
    assert t["lines_matched"] == ref["totals"]["lines_matched"]
    assert t["lines_skipped"] == ref["totals"]["lines_skipped"]


def test_max_reforms_exhausted_aborts_cleanly(corpus, tmp_path_factory):
    """--max-reforms 0 + an injected death: every survivor must abort with
    the clean budget-exhausted error in bounded time — no hangs."""
    td = tmp_path_factory.mktemp("elastic_budget")
    _td, prefix, shards = corpus
    outs = _spawn_launchers(
        td, prefix, shards, fault="tag=1,after_batches=4", max_reforms=0,
        timeout=300,
    )
    from ruleset_analysis_tpu.errors import EXIT_REFORM_BUDGET
    from ruleset_analysis_tpu.runtime.elastic import DIE_RC

    # normally the injected death (77); if an unrelated generation failure
    # raced ahead, the victim aborts on the exhausted budget instead —
    # either way it exited, cleanly and bounded, with the documented
    # failure-class exit code (7 = reform budget exhausted)
    assert outs[1][0] in (DIE_RC, EXIT_REFORM_BUDGET), outs[1][2][-1500:]
    for pid in (0, 2, 3):
        rc, _out, err = outs[pid]
        assert rc == EXIT_REFORM_BUDGET, (
            f"launcher {pid} rc={rc} (want {EXIT_REFORM_BUDGET})"
        )
        assert "budget exhausted" in err, err[-1500:]
    # no report: the run never completed
    assert not (td / "rep0.json").exists()
