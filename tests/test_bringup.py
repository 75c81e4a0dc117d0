"""Bring-up guards: the chip smoke rehearsed on the CPU, no CPU fallback,
one process per chip, and a compile cache placed from outside.

The smoke's phases run here at a tiny size on the 8 fake CPU devices, with
Pallas interpreted: that proves paths, arguments and the comparison
against the oracle.  What only a chip shows (compiled kernels, times) is
the smoke's own job on the chip.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

import chip_smoke
from cpuenv import REPO, cpu_env
from ruleset_analysis_tpu import errors
from ruleset_analysis_tpu.parallel import distributed

TINY = chip_smoke.Size(acls=2, rules=24, lines=6000, batch=1024, window=1000)


@pytest.fixture
def in_tmp(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)  # the CLI's relative outputs land here
    return str(tmp_path)


def test_smoke_one_chip_phases_match_oracle_on_cpu(in_tmp):
    compiled = chip_smoke.one_chip(in_tmp, TINY, seed=0, workers=2, serve_timeout=120.0)
    # the CPU backend interprets Pallas, and the smoke can tell
    assert compiled is False


def test_smoke_mesh_phase_registers_bit_identical(in_tmp):
    import jax

    corpus = chip_smoke.make_corpus(in_tmp, TINY, seed=1)
    ref = chip_smoke.oracle_reference(corpus, workers=2)
    chip_smoke.mesh_phase(corpus, TINY, ref, jax.devices()[:4])


def test_smoke_compare_names_the_difference():
    ref = {"hits": {("fw1", "A", 1): 3}, "unused": [], "lines_total": 3}
    got = {"hits": {("fw1", "A", 1): 2}, "unused": [], "lines_total": 3}
    with pytest.raises(chip_smoke.SmokeFailure, match="1 rules' hits differ"):
        chip_smoke.compare(got, ref, "run")


def test_smoke_refuses_without_tpu(capsys):
    assert chip_smoke.main([]) == 2
    out = capsys.readouterr()
    assert '"ok"' not in out.out
    assert "needs 1 TPU chip(s)" in out.err


def test_smoke_refuses_away_from_the_repo(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = cpu_env(1)
    env.pop("PYTHONPATH")
    r = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=120,
    )
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "cannot import" in r.stderr


@pytest.mark.parametrize("platforms", ["", "tpu"])
def test_colocated_processes_refused_off_cpu(monkeypatch, platforms):
    monkeypatch.setenv("JAX_PLATFORMS", platforms)
    with pytest.raises(errors.ChipBindingError, match="2 processes on one host"):
        distributed.check_one_chip_per_process(2, "test mode")
    distributed.check_one_chip_per_process(1, "test mode")  # one is fine


def test_colocated_processes_allowed_on_cpu(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    distributed.check_one_chip_per_process(4, "test mode")


def test_loopback_distributed_init_refused_before_joining(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    called = []
    monkeypatch.setattr(
        distributed.jax.distributed, "initialize",
        lambda **kw: called.append(kw),
    )
    with pytest.raises(errors.ChipBindingError, match="loopback coordinator"):
        distributed.init_distributed("localhost:1234", 2, 0)
    assert not called
    assert distributed.is_loopback("127.0.0.1") and distributed.is_loopback("[::1]")
    assert not distributed.is_loopback("10.0.0.2")


def test_distserve_process_workers_refused_off_cpu(tmp_path, monkeypatch):
    from ruleset_analysis_tpu.config import (
        AnalysisConfig, DistServeConfig, ServeConfig,
    )
    from ruleset_analysis_tpu.runtime.distserve import DistServeDriver

    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    with pytest.raises(errors.ChipBindingError, match="--dist-workers process"):
        DistServeDriver(
            str(tmp_path / "missing"),
            AnalysisConfig(mesh_shape="hybrid"),
            ServeConfig(
                listen=("udp:127.0.0.1:0",), window_lines=100,
                serve_dir=str(tmp_path / "sd"),
            ),
            DistServeConfig(hosts=2, workers="process"),
        )


def test_spawned_feed_workers_import_no_jax():
    """Feeder and convert-fleet workers are spawned after the parent took
    the chip; importing what they run must not even import JAX."""
    code = (
        "import sys\n"
        "import ruleset_analysis_tpu.hostside.feeder\n"
        "import ruleset_analysis_tpu.hostside.convertfleet\n"
        "print('jax' in sys.modules)\n"
    )
    r = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, env=cpu_env(1),
        capture_output=True, text=True, timeout=120,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.strip() == "False"


def _cache_dir_in_child(env_dir: str | None) -> list:
    env = cpu_env(1)
    env["JAX_PLATFORMS"] = "tpu"  # import only: no backend starts
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    code = (
        "import json, jax\n"
        "from ruleset_analysis_tpu.runtime import compcache\n"
        "print(json.dumps([compcache.enable_persistent_cache(),"
        " jax.config.jax_compilation_cache_dir]))\n"
    )
    r = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=120,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_compile_cache_placed_by_environment(tmp_path):
    where = str(tmp_path / "x")
    assert _cache_dir_in_child(where) == [where, where]


def test_compile_cache_defaults_to_fixed_dir_in_checkout():
    fixed = os.path.join(REPO, ".jax_cache")
    assert _cache_dir_in_child(None) == [fixed, fixed]
    assert os.path.isdir(fixed)
