"""The run call's spans on the profiler's clock, and the counters beside them.

- a profiler session around a small ``run_stream_file`` / ``run_stream_wire``
  holds the call's spans on its host plane, each batch's spans under one
  ``batch`` id, on the same clock as the ops they issued;
- the ingest counters split ``produce_sec`` into ``parse_sec`` + ``pack_sec``
  and the call reports its set-up and finalize seconds;
- ``totals.compile`` counts the compile work JAX reports inside the call:
  some on a cold call, none on a warm repeat, a new shape under its
  function's name;
- the JSONL sink keeps its stall filter; JAX-free processes stay JAX-free.
"""

import glob
import json
import os
import subprocess
import sys
import time

import pytest

pytest.importorskip("jax")

from ruleset_analysis_tpu.config import AnalysisConfig, SketchConfig
from ruleset_analysis_tpu.hostside import aclparse, pack, synth
from ruleset_analysis_tpu.hostside import wire as wire_mod
from ruleset_analysis_tpu.runtime import obs
from ruleset_analysis_tpu.runtime.ingest import IngestStats
from ruleset_analysis_tpu.runtime.stream import run_stream_file, run_stream_wire

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CALL_SPANS = {"run.call", "run.setup", "run.finalize"}
BATCH_SPANS = {"ingest.produce", "ingest.pack", "step.dispatch", "step.drain", "step.fetch"}


@pytest.fixture(autouse=True)
def _obs_clean():
    obs._reset_for_tests()
    yield
    obs._reset_for_tests()


@pytest.fixture(scope="module")
def corpus(tmp_path_factory):
    td = tmp_path_factory.mktemp("spans")
    cfg_text = synth.synth_config(n_acls=3, rules_per_acl=9, seed=23)
    packed = pack.pack_rulesets([aclparse.parse_asa_config(cfg_text, "fw1")])
    lines = synth.render_syslog(packed, synth.synth_tuples(packed, 2000, seed=24), seed=25)
    log = str(td / "spans.log")
    with open(log, "w", encoding="utf-8") as f:
        f.write("\n".join(lines) + "\n")
    wirep = str(td / "spans.rawire")
    wire_mod.convert_logs(packed, [log], wirep, block_rows=512)
    return packed, log, wirep


def _cfg(batch_size=400, **sketch):
    sk = dict(cms_width=1 << 9, cms_depth=2, hll_p=5)
    sk.update(sketch)
    return AnalysisConfig(
        batch_size=batch_size, sketch=SketchConfig(**sk), prefetch_depth=2,
        stall_timeout_sec=30.0,
    )


def _run(inp, corpus, cfg):
    packed, log, wirep = corpus
    if inp == "wire":
        return run_stream_wire(packed, wirep, cfg, topk=5)
    return run_stream_file(packed, log, cfg, topk=5)


def _host_events(trace_dir: str) -> list[dict]:
    """The program's spans on the newest trace's host plane."""
    from jax.profiler import ProfileData

    path = max(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True),
               key=os.path.getmtime)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                name = ev.name.split("#", 1)[0]
                if name in CALL_SPANS | BATCH_SPANS | {"ingest.starved", "ingest.backpressure"}:
                    out.append({"name": name, "line": i, "start": ev.start_ns,
                                "end": ev.start_ns + ev.duration_ns,
                                "stats": {k: v for k, v in ev.stats}})
    return out


@pytest.mark.parametrize("inp", ["text", "wire"])
def test_profiler_trace_holds_the_call_spans(inp, corpus, tmp_path):
    import jax

    cfg = _cfg()
    _run(inp, corpus, cfg)  # compile outside the session
    with jax.profiler.trace(str(tmp_path)):
        rep = _run(inp, corpus, cfg)
    evs = _host_events(str(tmp_path))
    names = {e["name"] for e in evs}
    assert CALL_SPANS | BATCH_SPANS <= names
    by = {n: [e for e in evs if e["name"] == n] for n in names}
    # one id per batch, shared by the producer's and the step's spans
    chunks = set(range(rep.totals["chunks"]))
    for n in BATCH_SPANS - {"ingest.produce"}:
        assert {e["stats"]["batch"] for e in by[n]} == chunks, n
    # the producer's last span is the read that found the end of input
    ends = {e["stats"]["batch"] for e in by["ingest.produce"]} - chunks
    assert ends == {len(chunks)}
    # the call's own spans and the steps run on the dispatching thread,
    # the parse and pack on the producer's
    (call,) = by["run.call"]
    assert {e["line"] for e in by["step.dispatch"]} == {call["line"]}
    assert {e["line"] for e in by["ingest.produce"]} != {call["line"]}
    (setup,), (fin,) = by["run.setup"], by["run.finalize"]
    first = min(e["start"] for e in by["step.dispatch"])
    last = max(e["end"] for e in by["step.dispatch"])
    assert call["start"] <= setup["start"] <= setup["end"] <= first
    assert last <= fin["start"] <= fin["end"] <= call["end"]
    # batch k is parsed before step k is dispatched
    produced = {e["stats"]["batch"]: e["end"] for e in by["ingest.produce"]}
    for e in by["step.dispatch"]:
        assert produced[e["stats"]["batch"]] <= e["start"]


@pytest.mark.parametrize("inp", ["text", "wire"])
def test_parse_and_pack_sum_to_produce(inp, corpus):
    rep = _run(inp, corpus, _cfg())
    ing = rep.totals["ingest"]
    assert ing["parse_sec"] > 0 and ing["pack_sec"] > 0
    assert ing["parse_sec"] + ing["pack_sec"] == pytest.approx(ing["produce_sec"], abs=1e-9)
    ph = rep.totals["phases"]
    assert ph["setup_sec"] > 0 and ph["finalize_sec"] > 0


def test_ingest_stats_produce_is_parse_plus_pack():
    st = IngestStats()
    st.parse_sec, st.pack_sec = 0.123456, 0.0211111
    assert st.produce_sec == st.parse_sec + st.pack_sec
    d = st.to_dict()
    assert d["parse_sec"] + d["pack_sec"] == pytest.approx(d["produce_sec"], abs=1e-9)


def test_compile_counted_cold_zero_warm_and_named(corpus):
    # a sketch geometry no other test compiles, so the first call is cold
    cfg = _cfg(batch_size=352, cms_width=1 << 7)
    cold = _run("wire", corpus, cfg).totals["compile"]
    assert cold["events"] > 0 and cold["sec"] > 0
    warm = _run("wire", corpus, cfg).totals["compile"]
    assert warm == {"events": 0, "sec": 0.0, "fns": {}}
    # a new batch shape compiles the step again, under the same name
    again = _run("wire", corpus, _cfg(batch_size=368, cms_width=1 << 7)).totals["compile"]
    assert again["events"] > 0
    steps = {fn for fn in cold["fns"] if "lambda" in fn}
    assert steps and steps <= set(again["fns"])


def test_sustained_rate_prices_out_the_measured_compile(corpus):
    t = _run("wire", corpus, _cfg(batch_size=336, cms_width=1 << 7)).totals
    assert 0 < t["compile"]["sec"] < t["elapsed_sec"]
    lines = t["lines_total"]
    assert t["sustained_lines_per_sec"] == pytest.approx(
        lines / (t["elapsed_sec"] - t["compile"]["sec"]), rel=1e-2
    )


def test_stall_filter_applies_to_the_jsonl_sink_only(tmp_path):
    import jax

    obs.start_trace(str(tmp_path / "tr"), role="main")
    with jax.profiler.trace(str(tmp_path / "prof")):
        with obs.span("ingest.starved", obs.STALL_SPAN_MIN_SEC, batch=0):
            pass
        with obs.span("ingest.starved", obs.STALL_SPAN_MIN_SEC, batch=1):
            time.sleep(0.005)
    merged = obs.shutdown()
    with open(merged, encoding="utf-8") as f:
        jsonl = [e for e in json.load(f)["traceEvents"] if e["name"] == "ingest.starved"]
    assert [e["args"]["batch"] for e in jsonl] == [1]
    prof = [e for e in _host_events(str(tmp_path / "prof")) if e["name"] == "ingest.starved"]
    assert sorted(e["stats"]["batch"] for e in prof) == [0, 1]


def test_span_without_sinks_is_the_profiler_annotation():
    from jax.profiler import TraceAnnotation

    assert isinstance(obs.span("x", batch=3), TraceAnnotation)
    with obs.span("x", batch=3):
        pass


@pytest.mark.parametrize("armed", [False, True])
def test_spans_in_a_jax_free_process_stay_jax_free(armed, tmp_path):
    """Feeder workers open spans too; that must not import JAX there."""
    code = (
        "import sys\n"
        "from ruleset_analysis_tpu.runtime import obs\n"
        "with obs.span('feeder.parse', batch=1):\n"
        "    pass\n"
        "obs.shutdown()\n"
        "print('jax' in sys.modules)\n"
    )
    env = {k: v for k, v in os.environ.items() if k != obs.ENV_VAR}
    if armed:
        env[obs.ENV_VAR] = str(tmp_path)
    r = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.strip() == "False"
    if armed:
        with open(glob.glob(str(tmp_path / "trace-*.jsonl"))[0], encoding="utf-8") as f:
            assert any('"feeder.parse"' in ln for ln in f)
