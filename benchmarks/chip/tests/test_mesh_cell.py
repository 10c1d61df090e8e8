"""A CNN cell on four chips, end to end on the CPU: ``tiny-cnn-maps.dp4``
of the test manifest runs through ``cnn_serve`` over a four-device data
mesh of virtual CPU devices (``--xla_force_host_platform_device_count=4``,
set in a child process, since JAX fixes its devices when it starts).  Its
``correct`` holds, its answers equal those of the same images served on
one device, and an answer altered where it is produced, or answers handed
to the wrong chip's requests, read ``correct`` false.  The configuration
keeps a spatial map in every layer, so that its logits differ from image
to image (the per-sample norm makes an FC layer's 1x1 output its shift
alone).

Run by hand: ``python3 test_mesh_cell.py probe`` prints one JSON line."""
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = os.path.join(HERE, "tiny")
PEAKS = {"bf16_flops": 1e12, "int8_ops": 2e12, "hbm_bytes_per_s": 1e11}
CELL = "tiny-cnn-maps.dp4"
SEED = 3_000_000_019


def _manifest(root: str, chips: int) -> str:
    """The test manifest copied under ``root``, the mesh cell on ``chips``."""
    shutil.copytree(TINY, root, dirs_exist_ok=True)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        d = json.load(f)
    for w in d["workloads"]:
        if w["name"] == CELL:
            w["chips"] = chips
    with open(path, "w") as f:
        json.dump(d, f)
    return path


def _run(manifest: str, fault=None) -> tuple[dict, dict]:
    """One run of the mesh cell; returns its result line and what the
    system under test held: its mesh, bucket size, verdicts and sample."""
    import contextlib
    import io

    from harness.runner import main

    seen: dict = {"sample": []}

    def hook(system):
        eng = system.dep.engine
        devices = 0 if eng.mesh is None else int(eng.mesh.devices.size)
        seen.update(devices=devices, max_batch=eng.batcher.max_batch,
                    engines=system.engines)
        sample = system.sample

        def keep(check, seed):
            out = sample(check, seed)
            seen["sample"].extend(out)
            return out

        system.sample = keep
        if fault is not None:
            fault(system)

    out = io.StringIO()
    argv = ["--workload", CELL, "--seed", str(SEED), "--seconds", "1",
            "--trace", "0"]
    with contextlib.redirect_stdout(out):
        rc = main(argv, t_process=time.perf_counter(), manifest=manifest,
                  require_tpu=False, peaks=PEAKS, fault=hook)
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1]), seen


def _alter_answer(system):
    """Every answer altered where the engine splits a bucket's output."""
    runner = system.dep.engine.runner
    split = runner.split

    def altered(host, n):
        return [row + 1.0 for row in split(host, n)]

    runner.split = altered


def _swap_shards(system):
    """Each chip's rows handed to the next chip's requests."""
    import numpy as np

    runner = system.dep.engine.runner
    split = runner.split

    def swapped(host, n):
        return split(np.roll(host, host.shape[0] // 4, axis=0), n)

    runner.split = swapped


FAULTS = {"altered": _alter_answer, "swapped": _swap_shards}


def probe() -> dict:
    """The mesh cell, the same cell on one device, and the mesh cell with
    each fault planted; what each read, and where their answers differ."""
    import numpy as np

    tmp = tempfile.mkdtemp()
    try:
        mesh, seen4 = _run(_manifest(os.path.join(tmp, "four"), 4))
        one, seen1 = _run(_manifest(os.path.join(tmp, "one"), 1))
        bad = {name: _run(_manifest(os.path.join(tmp, name), 4), fault)[0]
               for name, fault in FAULTS.items()}
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    by_image = {img.tobytes(): v for img, v in seen1["sample"]}
    common = [(by_image[img.tobytes()], v) for img, v in seen4["sample"]
              if img.tobytes() in by_image]
    return dict(
        mesh=dict(correct=mesh["correct"], attempted=mesh["attempted"],
                  count=mesh["device"]["count"], checks=mesh["checks"],
                  metrics=sorted(mesh["metrics"]), devices=seen4["devices"],
                  max_batch=seen4["max_batch"],
                  engines=[{str(b): e for b, e in d.items()}
                           for d in seen4["engines"]]),
        one=dict(correct=one["correct"], devices=seen1["devices"],
                 max_batch=seen1["max_batch"]),
        bad={name: dict(correct=r["correct"], checks=r["checks"])
             for name, r in bad.items()},
        compared=len(common),
        distinct=len({v.tobytes() for _, v in common}),
        equal=all(np.array_equal(a, b) for a, b in common))


@pytest.fixture(scope="module")
def probed():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "") +
                          " --xla_force_host_platform_device_count=4").strip())
    p = subprocess.run([sys.executable, os.path.abspath(__file__), "probe"],
                       cwd=HERE, env=env, capture_output=True, text=True,
                       timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_mesh_cell_serves_over_four_devices_and_is_correct(probed):
    m = probed["mesh"]
    assert m["correct"] is True, m
    assert m["count"] == 4 and m["devices"] == 4
    # four chips, each the one-chip bucket of the configuration (4 rows)
    assert m["max_batch"] == 16 and probed["one"]["max_batch"] == 4
    assert m["metrics"] == ["images_per_s", "setup_s"]
    assert m["attempted"] > 0
    # each chip runs 4 rows, under the plan's verdict for batch 4
    assert all("4" in e for e in m["engines"])


def test_mesh_answers_equal_one_device(probed):
    assert probed["one"]["correct"] is True
    assert probed["one"]["devices"] == 0          # no mesh on one chip
    assert probed["compared"] >= 4 and probed["distinct"] >= 4, probed
    assert probed["equal"] is True


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_mesh_fault_is_not_correct(probed, fault):
    bad = probed["bad"][fault]
    assert bad["correct"] is False
    assert bad["checks"]["logit_err"]["value"] > bad["checks"]["logit_err"][
        "limit"]


if __name__ == "__main__" and sys.argv[1:] == ["probe"]:
    sys.path.insert(0, os.path.dirname(HERE))
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.dirname(HERE))), "src"))
    print(json.dumps(probe()), flush=True)
