"""Sharding-rule unit tests + data pipeline + compression + elastic logic.

Pure-logic tests run on the 1-device CPU mesh; PP runs in a subprocess
with 8 forced host devices.
"""
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import all_configs, make_plan
from repro.distributed.sharding import pspec_for


class FakeMesh:
    def __init__(self, shape):
        self.shape = shape


MESH = FakeMesh({"data": 16, "model": 16})
MESH_MP = FakeMesh({"pod": 2, "data": 16, "model": 16})


def _plan(multi=False):
    return make_plan({"pod": 2, "data": 16, "model": 16} if multi
                     else {"data": 16, "model": 16})


def test_tp_rules_divisible():
    plan = _plan()
    cfg = all_configs()["phi3-mini-3.8b"]
    # wq (d, Hp*hd): heads -> model
    spec = pspec_for((3072, 32 * 96), ("embed", "heads"), plan, MESH, cfg)
    assert spec == P("data", "model")
    # kv 32 % 16 == 0 -> sharded
    spec = pspec_for((3072, 32 * 96), ("embed", "kv_heads"), plan, MESH, cfg)
    assert spec == P("data", "model")


def test_kv_replication_when_indivisible():
    plan = _plan()
    cfg = all_configs()["yi-34b"]  # kv=8, tp=16
    spec = pspec_for((7168, 8 * 128), ("embed", "kv_heads"), plan, MESH, cfg)
    assert spec == P("data", None)


def test_expert_sharding_rules():
    plan = _plan()
    ds = all_configs()["deepseek-moe-16b"]   # 64 % 16 == 0 -> EP
    gr = all_configs()["granite-moe-3b-a800m"]  # 40 % 16 != 0 -> replicate E, TP d_ff
    assert pspec_for((64, 2048, 1408), ("expert", "embed", "mlp"), plan, MESH, ds) \
        == P("model", "data", None)  # mlp falls back: model consumed by expert
    assert pspec_for((40, 1536, 512), ("expert", "embed", "mlp"), plan, MESH, gr) \
        == P(None, "data", "model")


def test_duplicate_mesh_axis_guard():
    plan = _plan()
    cfg = all_configs()["phi3-mini-3.8b"]
    # cache (layers, batch, seq, kv, hd): kv sharded => cache_seq must yield
    spec = pspec_for((32, 256, 32768, 32, 96),
                     ("layers", "batch", "cache_seq", "kv_heads", None),
                     plan, MESH, cfg)
    assert spec == P(None, ("data",), "model", None, None)


def test_indivisible_batch_replicates():
    plan = _plan(multi=True)
    spec = pspec_for((1, 128), ("batch", None), plan, MESH_MP, None)
    assert spec == P(None, None)  # batch 1 % 32 != 0 -> replicated


def test_vocab_padding_multiple_of_tp():
    for arch, cfg in all_configs().items():
        assert cfg.padded_vocab % 256 == 0
        assert cfg.padded_vocab >= cfg.vocab


def test_plan_padded_heads():
    plan = _plan()
    assert plan.padded_heads(56) == 64   # yi
    assert plan.padded_heads(15) == 16   # smollm
    assert plan.padded_heads(32) == 32   # phi3


def test_data_pipeline_determinism_and_sharding():
    from repro.data.pipeline import Pipeline
    from repro.data.synthetic import lm_batch

    fn = lambda s, m: lm_batch(s, m, batch=8, seq=8, vocab=32, seed=1)
    p0 = Pipeline(fn, accum_steps=2, host_index=0, n_hosts=2).start(0)
    p1 = Pipeline(fn, accum_steps=2, host_index=1, n_hosts=2).start(0)
    (sm0, b0) = next(p0)
    (sm1, b1) = next(p1)
    assert sm0 == sm1 == (0, 0)
    assert b0["tokens"].shape == (4, 8)
    # shards are disjoint slices of the same global batch
    g = fn(0, 0)
    np.testing.assert_array_equal(b0["tokens"], g["tokens"][:4])
    np.testing.assert_array_equal(b1["tokens"], g["tokens"][4:])
    p0.stop(); p1.stop()
    # determinism across restarts
    p2 = Pipeline(fn, accum_steps=2, host_index=0, n_hosts=2).start(0)
    (_, b0b) = next(p2)
    np.testing.assert_array_equal(b0["tokens"], b0b["tokens"])
    p2.stop()


def test_gradient_compression_error_feedback():
    from repro.train.compression import (
        compress, compressed_allreduce, decompress, init_error_feedback)
    g = {"w": jnp.asarray(np.random.RandomState(0).randn(64, 64) * 0.01)}
    ef = init_error_feedback(g)
    # single-shot error is bounded by one quantization level
    lv, sc = compress(g["w"], 8)
    err = np.abs(np.asarray(decompress(lv, sc)) - np.asarray(g["w"])).max()
    assert err <= float(sc) * 0.5 + 1e-9
    # error feedback telescopes: mean of N compressed steps -> true mean
    total, total_q = np.zeros((64, 64)), np.zeros((64, 64))
    for i in range(50):
        gi = {"w": jnp.asarray(np.random.RandomState(i).randn(64, 64) * 0.01)}
        cq, ef = compressed_allreduce(gi, ef)
        total += np.asarray(gi["w"])
        total_q += np.asarray(cq["w"])
    rel = np.abs(total_q - total).max() / np.abs(total).max()
    assert rel < 0.05, f"error feedback failed to telescope: {rel}"


def test_elastic_assignment_properties():
    from repro.train.elastic import shard_assignment, straggler_backup
    n = 8
    a = shard_assignment(n, step=3, micro=1, global_batch=64)
    hosts = [h for h, _ in a]
    offs = [o for _, o in a]
    assert sorted(hosts) == list(range(n))     # every host assigned
    assert sorted(offs) == [i * 8 for i in range(n)]  # full coverage
    b = straggler_backup(3, n, step=0, micro=0)
    assert b != 3 and 0 <= b < n


PP_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.distributed.pipeline import make_pipeline_mesh, pipeline_apply

S, M, mb, d = 4, 8, 2, 16
mesh = make_pipeline_mesh(S, data=2)
key = jax.random.PRNGKey(0)
Ws = jax.random.normal(key, (S, d, d)) * 0.2

def stage_fn(w, x):
    return jnp.tanh(x @ w)

x = jax.random.normal(key, (M, mb, d))
with jax.set_mesh(mesh):
    y = pipeline_apply(stage_fn, Ws, x, mesh=mesh, n_microbatches=M)
# oracle: sequential application of all stages
ref = x
for s in range(S):
    ref = jnp.tanh(ref @ Ws[s])
np.testing.assert_allclose(np.asarray(y), np.asarray(ref), rtol=2e-5, atol=2e-5)
print("PIPELINE OK")
"""


@pytest.mark.slow
def test_pipeline_parallel_8dev_subprocess():
    env = dict(os.environ, PYTHONPATH="src")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run([sys.executable, "-c", PP_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=600,
                       cwd=os.path.dirname(os.path.dirname(__file__)))
    assert "PIPELINE OK" in p.stdout, p.stdout + p.stderr
