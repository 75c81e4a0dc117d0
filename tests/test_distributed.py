"""Multi-process (jax.distributed) path: 2 CPU processes == 1 process.

VERDICT round 2 #5: the multi-host layer needs a demonstrated cross-process
run, not a docstring.  Two worker processes join a local-coordinator
jax.distributed cluster (4 fake CPU devices each -> an 8-device global
mesh), each feeds its own half of the corpus (the input-split analog), and
the final register files must be BIT-IDENTICAL to a single-process run
over the whole corpus — registers are mergeable and order-invariant, so
how lines were split across processes cannot matter.
"""

import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

from ruleset_analysis_tpu.hostside import aclparse, pack, synth

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_WORKER = os.path.join(os.path.dirname(os.path.abspath(__file__)), "dist_worker.py")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _worker_env(n_local_devices: int) -> dict:
    from cpuenv import cpu_env

    return cpu_env(n_local_devices)


def _spawn_and_check(argvs, n_local_devices):
    """Run one process per argv; kill all on timeout; assert every rc==0."""
    procs = [
        subprocess.Popen(
            argv,
            env=_worker_env(n_local_devices),
            cwd=_REPO,
            stdout=subprocess.PIPE,
            stderr=subprocess.PIPE,
            text=True,
        )
        for argv in argvs
    ]
    outs = []
    for p in procs:
        try:
            out, err = p.communicate(timeout=300)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        outs.append((p.returncode, out, err))
    for rc, _out, err in outs:
        assert rc == 0, f"worker failed rc={rc}\nstderr:\n{err[-3000:]}"
    return outs


def _run_workers(n_procs, port, ruleset_prefix, logs, out_prefixes,
                 n_local_devices, extra=()):
    _spawn_and_check(
        [
            [sys.executable, _WORKER, str(pid), str(n_procs), str(port),
             ruleset_prefix, logs[pid], out_prefixes[pid], *extra]
            for pid in range(n_procs)
        ],
        n_local_devices,
    )


@pytest.fixture(scope="module")
def corpus(tmp_path_factory):
    td = tmp_path_factory.mktemp("dist")
    cfg_text = synth.synth_config(
        n_acls=3, rules_per_acl=8, seed=41, egress_acls=True
    )
    rs = aclparse.parse_asa_config(cfg_text, "fw1")
    packed = pack.pack_rulesets([rs])
    tuples = synth.synth_tuples(packed, 1200, seed=42)
    lines = synth.render_syslog(packed, tuples, seed=43, variety=0.4)
    prefix = str(td / "packed")
    pack.save_packed(packed, prefix)
    full = td / "full.log"
    full.write_text("\n".join(lines) + "\n", encoding="utf-8")
    half0 = td / "half0.log"
    half0.write_text("\n".join(lines[:600]) + "\n", encoding="utf-8")
    half1 = td / "half1.log"
    half1.write_text("\n".join(lines[600:]) + "\n", encoding="utf-8")
    return td, prefix, str(full), str(half0), str(half1)


def test_two_process_registers_bit_identical_to_single(corpus):
    td, prefix, full, half0, half1 = corpus

    # reference: ONE process over the whole corpus (same driver code path)
    _run_workers(1, _free_port(), prefix, [full], [str(td / "ref")], 8)

    # two processes, 4 local fake devices each -> 8-device global mesh
    port = _free_port()
    _run_workers(2, port, prefix, [half0, half1],
                 [str(td / "out0"), str(td / "out1")], 4)

    ref = np.load(str(td / "ref.npz"))
    o0 = np.load(str(td / "out0.npz"))
    o1 = np.load(str(td / "out1.npz"))
    for k in ref.files:
        np.testing.assert_array_equal(ref[k], o0[k], err_msg=f"register {k}")
        np.testing.assert_array_equal(o0[k], o1[k], err_msg=f"register {k} ranks")

    rep_ref = json.loads((td / "ref.json").read_text())
    rep0 = json.loads((td / "out0.json").read_text())
    rep1 = json.loads((td / "out1.json").read_text())
    hits = lambda r: {tuple(e["key"]) if "key" in e else (e["firewall"], e["acl"], e["index"]): e["hits"] for e in r["per_rule"]}  # noqa: E731
    assert hits(rep0) == hits(rep_ref) == hits(rep1)
    assert rep0["unused"] == rep_ref["unused"]
    assert rep0["totals"]["lines_total"] == rep_ref["totals"]["lines_total"]
    assert rep0["totals"]["lines_matched"] == rep_ref["totals"]["lines_matched"]
    assert rep0["totals"]["processes"] == 2


def test_two_process_checkpoint_crash_resume(corpus):
    """Crash after 3 chunks (snapshot every 2), resume, finish: registers
    must be bit-identical to an uninterrupted 2-process run."""
    td, prefix, full, half0, half1 = corpus
    ck = str(td / "ck")

    # uninterrupted reference (2 processes, no checkpointing)
    _run_workers(2, _free_port(), prefix, [half0, half1],
                 [str(td / "u0"), str(td / "u1")], 4)

    # crash mid-run, then resume from the per-process snapshots
    _run_workers(2, _free_port(), prefix, [half0, half1],
                 [str(td / "c0"), str(td / "c1")], 4, extra=(ck, "crash"))
    assert os.path.isdir(os.path.join(ck, "proc-0-of-2"))
    assert os.path.isdir(os.path.join(ck, "proc-1-of-2"))
    _run_workers(2, _free_port(), prefix, [half0, half1],
                 [str(td / "r0"), str(td / "r1")], 4, extra=(ck, "resume"))

    ref = np.load(str(td / "u0.npz"))
    res = np.load(str(td / "r0.npz"))
    for k in ref.files:
        np.testing.assert_array_equal(ref[k], res[k], err_msg=f"register {k}")
    rep_u = json.loads((td / "u0.json").read_text())
    rep_r = json.loads((td / "r0.json").read_text())
    assert rep_r["unused"] == rep_u["unused"]
    assert rep_r["totals"]["lines_total"] == rep_u["totals"]["lines_total"]
    assert rep_r["totals"]["lines_matched"] == rep_u["totals"]["lines_matched"]


def test_stale_foreign_layout_dirs_do_not_block_resume(corpus, tmp_path):
    """proc-*-of-M leftovers must not block a valid proc-*-of-N resume."""
    from ruleset_analysis_tpu.runtime.stream import _dist_ckpt_layout_error

    ck = tmp_path / "ck"
    (ck / "proc-0-of-2").mkdir(parents=True)
    (ck / "proc-1-of-2").mkdir()
    # only foreign dirs -> resuming with 4 processes must refuse
    assert _dist_ckpt_layout_error(str(ck), 4) is not None
    # matching dirs present -> stale foreign dirs are ignored
    (ck / "proc-0-of-4").mkdir()
    assert _dist_ckpt_layout_error(str(ck), 4) is None
    # matching layout, no foreign -> fine
    assert _dist_ckpt_layout_error(str(ck), 2) is None


def test_cli_distributed_two_processes(corpus):
    """The run --distributed CLI path end-to-end across two processes."""
    td, prefix, full, half0, half1 = corpus
    port = _free_port()
    out0 = td / "cli_rep.json"
    _spawn_and_check(
        [
            [sys.executable, "-m", "ruleset_analysis_tpu.cli", "run",
             "--ruleset", prefix, "--logs", log, "--backend", "tpu",
             "--distributed", "--coordinator", f"127.0.0.1:{port}",
             "--num-processes", "2", "--process-id", str(pid),
             "--batch-size", "64", "--json", "--out", str(out0)]
            for pid, log in ((0, half0), (1, half1))
        ],
        4,
    )
    # only rank 0 writes the report (rank 1 returns before output)
    rep = json.loads(out0.read_text(encoding="utf-8"))
    assert rep["totals"]["processes"] == 2
    assert rep["totals"]["lines_total"] == 1200


def test_two_process_stacked_layout(corpus):
    """Stacked (per-ACL slab) layout across two processes: the mergeable
    registers that don't depend on chunk boundaries (counts, cms, hll)
    must be bit-identical to the flat 2-process run's."""
    td, prefix, full, half0, half1 = corpus

    _run_workers(2, _free_port(), prefix, [half0, half1],
                 [str(td / "st0"), str(td / "st1")], 4, extra=("-", "stacked"))

    # flat reference already produced by the first test (module fixture
    # ordering isn't guaranteed, so recompute if missing)
    if not (td / "out0.npz").exists():
        _run_workers(2, _free_port(), prefix, [half0, half1],
                     [str(td / "out0"), str(td / "out1")], 4)

    flat = np.load(str(td / "out0.npz"))
    st0 = np.load(str(td / "st0.npz"))
    st1 = np.load(str(td / "st1.npz"))
    for k in ("counts_lo", "counts_hi", "cms", "hll", "talk_cms"):
        np.testing.assert_array_equal(flat[k], st0[k], err_msg=f"register {k}")
        np.testing.assert_array_equal(st0[k], st1[k], err_msg=f"register {k} ranks")
    rep_flat = json.loads((td / "out0.json").read_text())
    rep_st = json.loads((td / "st0.json").read_text())
    hits = lambda r: {(e["firewall"], e["acl"], e["index"]): e["hits"] for e in r["per_rule"]}  # noqa: E731
    assert hits(rep_st) == hits(rep_flat)
    assert rep_st["unused"] == rep_flat["unused"]
    assert rep_st["totals"]["lines_total"] == rep_flat["totals"]["lines_total"]
    assert rep_st["totals"]["lines_matched"] == rep_flat["totals"]["lines_matched"]


def _ensure_ref(corpus):
    """Single-process reference registers (recompute if test order skipped it)."""
    td, prefix, full, _h0, _h1 = corpus
    if not (td / "ref.npz").exists():
        _run_workers(1, _free_port(), prefix, [full], [str(td / "ref")], 8)
    return np.load(str(td / "ref.npz")), json.loads((td / "ref.json").read_text())


def test_four_process_uneven_splits_including_empty(corpus):
    """VERDICT r3 #6: 4 processes, strongly uneven input splits (700/300/
    200/0 lines — one process has NOTHING).  The collective loop pads dry
    processes, so registers must still be bit-identical to 1 process."""
    td, prefix, full, _h0, _h1 = corpus
    lines = open(full, encoding="utf-8").read().splitlines()
    sizes = (700, 300, 200, 0)
    splits, pos = [], 0
    for i, n in enumerate(sizes):
        p = td / f"q{i}.log"
        p.write_text("".join(ln + "\n" for ln in lines[pos : pos + n]), encoding="utf-8")
        splits.append(str(p))
        pos += n
    assert pos == len(lines)

    _run_workers(4, _free_port(), prefix, splits,
                 [str(td / f"q{i}") for i in range(4)], 2)
    ref, rep_ref = _ensure_ref(corpus)
    outs = [np.load(str(td / f"q{i}.npz")) for i in range(4)]
    for k in ref.files:
        for i, o in enumerate(outs):
            np.testing.assert_array_equal(ref[k], o[k], err_msg=f"register {k} rank {i}")
    rep = json.loads((td / "q0.json").read_text())
    assert rep["totals"]["processes"] == 4
    assert rep["totals"]["lines_total"] == rep_ref["totals"]["lines_total"]
    assert rep["totals"]["lines_matched"] == rep_ref["totals"]["lines_matched"]
    assert rep["unused"] == rep_ref["unused"]


@pytest.mark.slow  # widest fake mesh; 4-process uneven covers multi>2 in tier-1
def test_eight_process_registers_match_single(corpus):
    """8 processes x 1 fake device each == the SURVEY §5 fake-mesh idiom
    at its widest; registers bit-identical to the single-process run."""
    td, prefix, full, _h0, _h1 = corpus
    lines = open(full, encoding="utf-8").read().splitlines()
    splits = []
    for i in range(8):
        p = td / f"e{i}.log"
        p.write_text("".join(ln + "\n" for ln in lines[i * 150 : (i + 1) * 150]),
                     encoding="utf-8")
        splits.append(str(p))

    _run_workers(8, _free_port(), prefix, splits,
                 [str(td / f"e{i}") for i in range(8)], 1)
    ref, _rep_ref = _ensure_ref(corpus)
    o0 = np.load(str(td / "e0.npz"))
    o7 = np.load(str(td / "e7.npz"))
    for k in ref.files:
        np.testing.assert_array_equal(ref[k], o0[k], err_msg=f"register {k}")
        np.testing.assert_array_equal(o0[k], o7[k], err_msg=f"register {k} ranks")
    rep = json.loads((td / "e0.json").read_text())
    assert rep["totals"]["processes"] == 8
    assert rep["totals"]["lines_total"] == 1200


@pytest.mark.slow  # ~100s: jax-level detection needs its full heartbeat
# window on old jax; the elastic tier tests cover peer death in tier-1
def test_killed_process_fails_cleanly_not_hangs(corpus):
    """SURVEY §6 failure detection: when a peer dies abruptly mid-job, the
    survivor must abort with an error in bounded time (heartbeat-driven
    dead-peer detection), never hang in a collective."""
    import time

    td, prefix, full, half0, half1 = corpus
    port = _free_port()
    env = _worker_env(4)
    args = lambda pid, mode: [  # noqa: E731
        sys.executable, _WORKER, str(pid), "2", str(port),
        prefix, half0 if pid == 0 else half1,
        str(td / f"k{pid}"), "-", mode,
    ]
    t0 = time.monotonic()
    survivor = subprocess.Popen(args(0, "survivor"), env=env, cwd=_REPO,
                                stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                                text=True)
    victim = subprocess.Popen(args(1, "die"), env=env, cwd=_REPO,
                              stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                              text=True)
    try:
        _out, verr = victim.communicate(timeout=120)
        assert victim.returncode == 3, verr[-2000:]
        # survivor must FAIL (nonzero) well before the 180s ceiling:
        # heartbeat timeout is 10s, so detection lands in tens of seconds
        _out, serr = survivor.communicate(timeout=180)
    except subprocess.TimeoutExpired:
        survivor.kill()
        victim.kill()
        raise AssertionError("survivor hung after peer death (no bounded-time failure)")
    elapsed = time.monotonic() - t0
    assert survivor.returncode != 0, "survivor reported success despite a dead peer"
    assert elapsed < 180, f"survivor took {elapsed:.0f}s to fail"
    # it died on a real error surface, not a silent exit
    assert serr.strip(), "survivor produced no error output"


@pytest.mark.slow  # stacked snapshot barrier also covered by flat crash +
# single-process stacked tests in tier-1
def test_two_process_stacked_checkpoint_crash_resume(corpus):
    """VERDICT r3 #4: checkpoint/resume on the stacked distributed path.
    Snapshots are collective flush barriers, so crash+resume registers are
    bit-identical to an uninterrupted stacked 2-process run."""
    td, prefix, full, half0, half1 = corpus
    ck = str(td / "ck_st")

    # uninterrupted stacked reference (no checkpointing)
    if not (td / "st0.npz").exists():
        _run_workers(2, _free_port(), prefix, [half0, half1],
                     [str(td / "st0"), str(td / "st1")], 4, extra=("-", "stacked"))

    _run_workers(2, _free_port(), prefix, [half0, half1],
                 [str(td / "sc0"), str(td / "sc1")], 4,
                 extra=(ck, "stacked-crash"))
    assert os.path.isdir(os.path.join(ck, "proc-0-of-2"))
    assert os.path.isdir(os.path.join(ck, "proc-1-of-2"))
    _run_workers(2, _free_port(), prefix, [half0, half1],
                 [str(td / "sr0"), str(td / "sr1")], 4,
                 extra=(ck, "stacked-resume"))

    ref = np.load(str(td / "st0.npz"))
    r0 = np.load(str(td / "sr0.npz"))
    r1 = np.load(str(td / "sr1.npz"))
    # order-invariant registers must be bit-identical (candidate tables
    # are chunk-boundary-sensitive by design and excluded)
    for k in ("counts_lo", "counts_hi", "cms", "hll", "talk_cms"):
        np.testing.assert_array_equal(ref[k], r0[k], err_msg=f"register {k}")
        np.testing.assert_array_equal(r0[k], r1[k], err_msg=f"register {k} ranks")
    rep_ref = json.loads((td / "st0.json").read_text())
    rep_r = json.loads((td / "sr0.json").read_text())
    hits = lambda r: {(e["firewall"], e["acl"], e["index"]): e["hits"] for e in r["per_rule"]}  # noqa: E731
    assert hits(rep_r) == hits(rep_ref)
    assert rep_r["unused"] == rep_ref["unused"]
    assert rep_r["totals"]["lines_total"] == rep_ref["totals"]["lines_total"]
    assert rep_r["totals"]["lines_matched"] == rep_ref["totals"]["lines_matched"]


def test_two_process_wire_input_matches_text(corpus):
    """The distributed path over pre-tokenized .rawire splits: registers
    and raw-line totals must match the text-input distributed run."""
    from ruleset_analysis_tpu.hostside import wire

    td, prefix, full, half0, half1 = corpus
    packed = pack.load_packed(prefix)
    w0, w1 = str(td / "half0.rawire"), str(td / "half1.rawire")
    wire.convert_logs(packed, [half0], w0, block_rows=64)
    wire.convert_logs(packed, [half1], w1, block_rows=64)

    if not (td / "out0.npz").exists():
        _run_workers(2, _free_port(), prefix, [half0, half1],
                     [str(td / "out0"), str(td / "out1")], 4)
    _run_workers(2, _free_port(), prefix, [w0, w1],
                 [str(td / "w0"), str(td / "w1")], 4)

    ref = np.load(str(td / "out0.npz"))
    got0 = np.load(str(td / "w0.npz"))
    got1 = np.load(str(td / "w1.npz"))
    for k in ref.files:
        np.testing.assert_array_equal(ref[k], got0[k], err_msg=f"register {k}")
        np.testing.assert_array_equal(got0[k], got1[k], err_msg=f"register {k} ranks")
    rep_ref = json.loads((td / "out0.json").read_text())
    rep_w = json.loads((td / "w0.json").read_text())
    hits = lambda r: {(e["firewall"], e["acl"], e["index"]): e["hits"] for e in r["per_rule"]}  # noqa: E731
    assert hits(rep_w) == hits(rep_ref)
    assert rep_w["unused"] == rep_ref["unused"]
    # totals_patch restores the converter's raw-line accounting per split
    assert rep_w["totals"]["lines_total"] == rep_ref["totals"]["lines_total"]
    assert rep_w["totals"]["lines_matched"] == rep_ref["totals"]["lines_matched"]
    assert rep_w["totals"]["lines_skipped"] == rep_ref["totals"]["lines_skipped"]


def test_stacked_abort_drains_buffered_lines(corpus):
    """max_chunks abort in stacked mode: lines already counted into the
    totals must still reach the registers (collective post-abort drain)."""
    td, prefix, full, half0, half1 = corpus
    _run_workers(2, _free_port(), prefix, [half0, half1],
                 [str(td / "sa0"), str(td / "sa1")], 4,
                 extra=("-", "stacked-abort"))
    regs = np.load(str(td / "sa0.npz"))
    rep = json.loads((td / "sa0.json").read_text())
    total_counts = int(
        regs["counts_lo"].astype(np.uint64).sum()
        + (regs["counts_hi"].astype(np.uint64).sum() << np.uint64(32))
    )
    # every counted evaluation landed in the registers — no limbo lines
    assert total_counts == rep["totals"]["lines_matched"]
    assert 0 < rep["totals"]["lines_total"] < 1200  # genuinely aborted early


def test_corrupt_one_process_snapshot_fails_all_loudly(corpus):
    """Resume where ONE process's snapshot is corrupt: every process must
    exit with the typed CheckpointCorrupt verdict in bounded time — a
    lone local raise would strand the peers in the resume allgather
    (stream.py evaluates all local conditions first, gathers once, then
    raises the same verdict everywhere)."""
    td, prefix, full, half0, half1 = corpus
    ck = str(td / "ck-corrupt")

    _run_workers(2, _free_port(), prefix, [half0, half1],
                 [str(td / "x0"), str(td / "x1")], 4, extra=(ck, "crash"))
    # corrupt proc-1's snapshot payload (keep the pointer intact)
    pdir = os.path.join(ck, "proc-1-of-2")
    latest = open(os.path.join(pdir, "LATEST")).read().strip()
    state = os.path.join(pdir, latest, "state.npz")
    with open(state, "r+b") as f:
        f.write(b"\xde\xad\xbe\xef" * 8)

    port = _free_port()
    procs = [
        subprocess.Popen(
            [sys.executable, _WORKER, str(pid), "2", str(port),
             prefix, [half0, half1][pid], str(td / f"z{pid}"), ck, "resume"],
            env=_worker_env(4), cwd=_REPO,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        )
        for pid in range(2)
    ]
    errs = []
    for p in procs:
        try:
            _out, err = p.communicate(timeout=240)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise AssertionError("resume with a corrupt snapshot HUNG")
        errs.append((p.returncode, err))
    assert all(rc != 0 for rc, _ in errs), f"some worker succeeded: {errs}"
    assert any("CheckpointCorrupt" in err or "corrupt" in err for _, err in errs)


@pytest.fixture(scope="module")
def corpus6(tmp_path_factory):
    """Unified (v4+v6) corpus: exercises the distributed v6 side path."""
    import random as _random

    from ruleset_analysis_tpu.hostside import oracle as oracle_mod

    td = tmp_path_factory.mktemp("dist6")
    cfg_text = synth.synth_config(
        n_acls=3, rules_per_acl=8, seed=51, v6_fraction=0.4
    )
    rs = aclparse.parse_asa_config(cfg_text, "fw1")
    packed = pack.pack_rulesets([rs])
    assert packed.has_v6
    t4 = synth.synth_tuples(packed, 700, seed=52)
    t6 = synth.synth_tuples6(packed, 500, seed=53)
    lines = synth.render_syslog(packed, t4, seed=54) + synth.render_syslog6(
        packed, t6, seed=55
    )
    _random.Random(5).shuffle(lines)
    res = oracle_mod.Oracle([rs]).consume(list(lines))
    prefix = str(td / "packed")
    pack.save_packed(packed, prefix)
    (td / "full.log").write_text("\n".join(lines) + "\n", encoding="utf-8")
    (td / "half0.log").write_text("\n".join(lines[:600]) + "\n", encoding="utf-8")
    (td / "half1.log").write_text("\n".join(lines[600:]) + "\n", encoding="utf-8")
    return td, prefix, res


def test_two_process_v6_bit_identical_and_oracle_exact(corpus6):
    td, prefix, res = corpus6
    _run_workers(1, _free_port(), prefix, [str(td / "full.log")],
                 [str(td / "ref6")], 8)
    _run_workers(2, _free_port(), prefix,
                 [str(td / "half0.log"), str(td / "half1.log")],
                 [str(td / "o60"), str(td / "o61")], 4)
    ref = np.load(str(td / "ref6.npz"))
    o0 = np.load(str(td / "o60.npz"))
    o1 = np.load(str(td / "o61.npz"))
    for k in ref.files:
        np.testing.assert_array_equal(ref[k], o0[k], err_msg=f"register {k}")
        np.testing.assert_array_equal(o0[k], o1[k], err_msg=f"register {k} ranks")
    rep0 = json.loads((td / "o60.json").read_text())
    rep1 = json.loads((td / "o61.json").read_text())
    got = {
        (e["firewall"], e["acl"], e["index"]): e["hits"]
        for e in rep0["per_rule"] if e["hits"] > 0
    }
    assert got == dict(res.hits)
    # identical-everywhere contract holds for v6 talker rendering too
    assert rep0["talkers"] == rep1["talkers"]


@pytest.mark.slow  # v6 crash/resume is tier-1 single-process (test_stream6)
def test_two_process_v6_crash_resume(corpus6):
    td, prefix, res = corpus6
    ck = str(td / "ck6")
    _run_workers(2, _free_port(), prefix,
                 [str(td / "half0.log"), str(td / "half1.log")],
                 [str(td / "u60"), str(td / "u61")], 4)
    _run_workers(2, _free_port(), prefix,
                 [str(td / "half0.log"), str(td / "half1.log")],
                 [str(td / "c60"), str(td / "c61")], 4, extra=(ck, "crash"))
    _run_workers(2, _free_port(), prefix,
                 [str(td / "half0.log"), str(td / "half1.log")],
                 [str(td / "r60"), str(td / "r61")], 4, extra=(ck, "resume"))
    ref = np.load(str(td / "u60.npz"))
    got = np.load(str(td / "r60.npz"))
    for k in ref.files:
        np.testing.assert_array_equal(ref[k], got[k], err_msg=f"register {k}")


def test_two_process_v6_wire_input_matches_text(corpus6, tmp_path):
    """Distributed wire-v2 input: the phase-2 collective v6 rounds must
    reproduce the text run's registers exactly."""
    from ruleset_analysis_tpu.hostside import wire

    td, prefix, res = corpus6
    packed = pack.load_packed(prefix)
    w0 = str(tmp_path / "h0.rawire")
    w1 = str(tmp_path / "h1.rawire")
    wire.convert_logs(packed, [str(td / "half0.log")], w0)
    wire.convert_logs(packed, [str(td / "half1.log")], w1)
    r = wire.WireReader([w0, w1], packed)
    assert r.n6_rows > 0  # the v2 sections are actually exercised
    r.close()
    _run_workers(2, _free_port(), prefix, [w0, w1],
                 [str(tmp_path / "w60"), str(tmp_path / "w61")], 4)
    # reference: the 2-process TEXT run over the same halves
    _run_workers(2, _free_port(), prefix,
                 [str(td / "half0.log"), str(td / "half1.log")],
                 [str(tmp_path / "t60"), str(tmp_path / "t61")], 4)
    ref = np.load(str(tmp_path / "t60.npz"))
    got = np.load(str(tmp_path / "w60.npz"))
    for k in ref.files:
        # every register file, talk_cms included: all updates are
        # order-invariant merges, so text and wire phase order cannot
        # change the final state
        np.testing.assert_array_equal(ref[k], got[k], err_msg=f"register {k}")
    rep = json.loads((tmp_path / "w60.json").read_text())
    got_hits = {
        (e["firewall"], e["acl"], e["index"]): e["hits"]
        for e in rep["per_rule"] if e["hits"] > 0
    }
    assert got_hits == dict(res.hits)


@pytest.mark.slow  # stacked+v6 each covered separately in tier-1
def test_two_process_v6_stacked_bit_identical(corpus6, tmp_path):
    """Stacked layout + v6 side channel across 2 processes == 1 process."""
    td, prefix, res = corpus6
    _run_workers(1, _free_port(), prefix, [str(td / "full.log")],
                 [str(tmp_path / "sref")], 8, extra=("-", "stacked"))
    _run_workers(2, _free_port(), prefix,
                 [str(td / "half0.log"), str(td / "half1.log")],
                 [str(tmp_path / "s0"), str(tmp_path / "s1")], 4,
                 extra=("-", "stacked"))
    ref = np.load(str(tmp_path / "sref.npz"))
    o0 = np.load(str(tmp_path / "s0.npz"))
    for k in ref.files:
        np.testing.assert_array_equal(ref[k], o0[k], err_msg=f"register {k}")
    rep = json.loads((tmp_path / "s0.json").read_text())
    got_hits = {
        (e["firewall"], e["acl"], e["index"]): e["hits"]
        for e in rep["per_rule"] if e["hits"] > 0
    }
    assert got_hits == dict(res.hits)
