"""The real ``BENCHMARK.json``: every name it holds resolves to a file of
the harness, and each cell's metrics are the ones its readers can give."""
import os
import re

import pytest

from harness.common import BENCH_DIR, REPO_ROOT, Manifest, load_module

MANIFEST = Manifest.load(os.path.join(REPO_ROOT, "BENCHMARK.json"))
CELLS = [w["name"] for w in MANIFEST.data["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves(cell):
    w = MANIFEST.cell(cell)
    config = MANIFEST.config(w["config"])
    MANIFEST.traffic(w["traffic"])
    assert os.path.isfile(os.path.join(BENCH_DIR, "harness",
                                       config["driver"] + ".py"))
    assert hasattr(load_module(MANIFEST.find("reference", config["reference"],
                                             ".py")), "init_params")
    for trace in (False, True):
        metrics = MANIFEST.metrics_for(cell, trace)
        assert metrics, (cell, trace)
        for m in metrics:
            reader = load_module(MANIFEST.find("metrics", m["name"], ".py"))
            assert callable(reader.read), m["name"]
    e2e = {m["name"] for m in MANIFEST.metrics_for(cell, False)}
    assert "setup_s" in e2e and len(e2e) >= 2


def test_names_files_and_bounds():
    d = MANIFEST.data
    with open(os.path.join(REPO_ROOT, "BENCHMARK.json"), "rb") as f:
        assert len(f.read()) <= 64 * 1024
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in d[group]]
        assert len(set(names)) == len(names), group
        assert all(NAME.match(n) for n in names), group
    files = [c["file"] for c in d["configs"]]
    assert len(set(files)) == len(files)
    for c in d["configs"]:
        assert c["file"].startswith(tuple(p + "/" for p in d["paths"]))
        assert any(w["config"] == c["name"] for w in d["workloads"])
    for m in d["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25, m["name"]
        assert set(m.get("workloads", CELLS)) <= set(CELLS), m["name"]


def test_per_layer_metrics_list_real_cells_that_report_what_they_move():
    e2e = {m["name"]: m for m in MANIFEST.data["end_to_end"]}
    for m in MANIFEST.data["per_layer"]:
        assert m["workloads"] and set(m["workloads"]) <= set(CELLS), m["name"]
        moved = e2e[m["moves"]]
        assert set(m["workloads"]) <= set(moved.get("workloads", CELLS)), \
            m["name"]



def test_lm_cell_traced_metrics_read_numbers_from_recorded_trace():
    """Every per-layer metric of the LM cell reads a number from the
    chip trace recorded with the cell (one prefill chunk, one decode
    step), the roofline and the idle share as shares below 100%."""
    import json

    from harness.common import Run, load_peaks
    from harness.trace import Trace

    cell = MANIFEST.cell("smollm-360m-w1a8.chat-offline")
    with open(os.path.join(BENCH_DIR, "tests", "data",
                           "smollm_chat_offline_trace.json")) as f:
        data = json.load(f)
    trace = Trace(data)
    run = Run(cell=cell, config=MANIFEST.config(cell["config"]),
              traffic=MANIFEST.traffic(cell["traffic"]), t0=0.0,
              t1=trace.window_s, trace=trace, spans=data["spans"],
              peaks=load_peaks("TPU v5 lite"))
    run.find = MANIFEST.find
    run.trace_span = (0.0, trace.window_s)
    got = {m["name"]: load_module(MANIFEST.find("metrics", m["name"], ".py")
                                  ).read(run)
           for m in MANIFEST.metrics_for(cell["name"], True)}
    assert set(got) == {"decode_step_ms", "prefill_chunk_ms", "mfu.lm_decode",
                        "attn_paged_roofline", "idle_share.lm"}
    assert all(v is not None and v > 0 for v in got.values()), got
    assert got["decode_step_ms"] == pytest.approx(
        1e3 * data["spans"]["decode_step"][0]["dt"])
    assert got["attn_paged_roofline"] < 100 and got["idle_share.lm"] < 100
