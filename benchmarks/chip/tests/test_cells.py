"""The harness end to end on the CPU, on cells that exist only in the
test manifest ``tiny/BENCHMARK.json``: a new cell, configuration and
traffic mix are files and entries only.  Each cell also runs with a fault
planted under the timed path, and ``correct`` must come out false; the
control (the reference at the lower precision) must fail its limit."""
import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

from harness.common import BENCH_DIR, REPO_ROOT
from harness.runner import main

TINY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "tiny")
PEAKS = {"bf16_flops": 1e12, "int8_ops": 2e12, "hbm_bytes_per_s": 1e11}


@pytest.fixture(scope="module")
def manifest(tmp_path_factory):
    d = tmp_path_factory.mktemp("bench")
    shutil.copytree(TINY, d, dirs_exist_ok=True)
    return str(d / "BENCHMARK.json")


def run_cell(manifest, capsys, workload, *, trace=0, control=0, fault=None,
             seed=3_000_000_017):
    argv = ["--workload", workload, "--seed", str(seed), "--seconds", "1",
            "--trace", str(trace), "--control", str(control)]
    rc = main(argv, t_process=time.perf_counter(), manifest=manifest,
              require_tpu=False, peaks=PEAKS, fault=fault)
    out = capsys.readouterr()
    assert rc == 0, out.err[-2000:]
    return json.loads(out.out.strip().splitlines()[-1])


@pytest.mark.parametrize("workload,metric", [
    ("tiny-cnn.closed", "images_per_s"),
    ("tiny-cnn.open", "image_p99_ms"),
])
def test_cnn_cell_from_test_manifest(manifest, capsys, workload, metric):
    r = run_cell(manifest, capsys, workload, control=1)
    assert r["correct"] is True, r
    assert set(r["metrics"]) == {metric, "setup_s"}
    assert r["attempted"] > 0 and r["failed"] == 0
    assert list(r)[-1] == "checks"
    # the control: full-precision layers through float8 fail the limit
    assert r["control_logit_err"] > r["checks"]["logit_err"]["limit"]


@pytest.mark.parametrize("workload,metrics,control", [
    ("tiny-lm.open", {"ttft_p90_ms", "itl_p99_ms", "setup_s"}, 1),
    ("tiny-lm.closed", {"tokens_per_s", "setup_s"}, 0),
])
def test_lm_cell_from_test_manifest(manifest, capsys, workload, metrics,
                                    control):
    r = run_cell(manifest, capsys, workload, control=control)
    assert r["correct"] is True, r
    assert set(r["metrics"]) == metrics
    assert r["attempted"] > 0 and r["failed"] == 0
    if control:
        # the control: int4 activation levels fail the limit (the closed
        # cell's sample, which requests finish in a second on the CPU,
        # varies too much at this size for the control to read alike)
        assert r["control_token_gap"] > r["checks"]["token_gap"]["limit"]


def _alter_answer(system):
    runner = system.dep.engine.runner
    split = runner.split

    def altered(host, n):
        rows = split(host, n)
        rows[0] = rows[0] + 1.0
        return rows

    runner.split = altered


def _alter_token(system):
    e = system.engine
    dispatch = e._dispatch

    def altered(table_rows, toks, pos, valid):
        logits = dispatch(table_rows, toks, pos, valid).copy()
        logits[..., 3] = np.inf          # every step's answer is token 3
        return logits

    e._dispatch = altered


def _state_unchanged(system):
    e = system.engine
    run_fn = e._run_fn

    def stale(params, pools, *args):
        logits, _ = run_fn(params, pools, *args)
        return logits, pools             # the KV pools never written

    e._run_fn = stale


@pytest.mark.parametrize("workload,fault", [
    ("tiny-cnn.closed", _alter_answer),
    ("tiny-lm.open", _alter_token),
    ("tiny-lm.open", _state_unchanged),
    ("tiny-lm.closed", _alter_token),
    ("tiny-lm.closed", _state_unchanged),
])
def test_fault_under_timed_path_is_not_correct(manifest, capsys, workload,
                                               fault):
    r = run_cell(manifest, capsys, workload, fault=fault)
    assert r["correct"] is False, r


def test_no_tpu_exits_nonzero_without_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, "run.py"), "--workload",
         "alexnet-w1a4.batch", "--seed", "1", "--seconds", "1", "--trace",
         "0"], cwd=REPO_ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copy(os.path.join(REPO_ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH_DIR, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "alexnet-w1a4.batch", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, env=dict(os.environ),
        capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
