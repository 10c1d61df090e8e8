"""GPipe-style pipeline parallelism via shard_map + collective_permute.

The production 2x16x16 mesh covers every assigned model with TP x DP (no
arch needs more than 16-way model sharding), so PP is an *optional* axis:
``make_pipeline_mesh(stages, data)`` builds ("pipe", "data") meshes and
``pipeline_apply`` runs a stage-partitioned layer stack with microbatched
1F1B-ish scheduling (forward-only steady state here; the backward pass is
driven by JAX AD through the shard_map).

Exercised by tests/test_pipeline.py on an 8-device host mesh (subprocess
with XLA_FLAGS=--xla_force_host_platform_device_count=8).
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def make_pipeline_mesh(stages: int, data: int = 1):
    return jax.make_mesh((stages, data), ("pipe", "data"))


def pipeline_apply(stage_fn: Callable, stage_params, x, *, mesh,
                   n_microbatches: int):
    """Run ``y = stage_L(...stage_1(x))`` over the "pipe" mesh axis.

    stage_params: pytree with leading stage axis (sharded over "pipe").
    x: (n_microbatches, mb, ...) activations (microbatch-major).
    Schedule: standard GPipe fill-drain of T = M + S - 1 ticks; at tick t,
    stage s processes microbatch t - s. Bubble fraction = (S-1)/(M+S-1).
    """
    S = mesh.shape["pipe"]
    M = n_microbatches

    def per_stage(params, xs):
        # params: this stage's params (leading axis 1); xs: (M, mb, ...)
        params = jax.tree.map(lambda t: t[0], params)
        stage_id = jax.lax.axis_index("pipe")
        mb_shape = xs.shape[1:]
        ticks = M + S - 1

        def tick(carry, t):
            buf, outs = carry  # buf: activation entering this stage
            # stage 0 feeds from xs[t] while t < M, others from the permuted buf
            feed = jnp.where(
                t < M,
                jax.lax.dynamic_index_in_dim(xs, jnp.minimum(t, M - 1), 0,
                                             keepdims=False),
                jnp.zeros(mb_shape, xs.dtype))
            inp = jnp.where(stage_id == 0, feed, buf)
            out = stage_fn(params, inp)
            # pass activations down the pipe: stage s -> s+1
            nxt = jax.lax.ppermute(
                out, "pipe", [(i, i + 1) for i in range(S - 1)])
            # last stage records its output for microbatch t - (S-1)
            mb_idx = t - (S - 1)
            outs = jax.lax.cond(
                mb_idx >= 0,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, out, jnp.maximum(mb_idx, 0), 0),
                lambda o: o, outs)
            return (nxt, outs), None

        buf0 = jnp.zeros(mb_shape, xs.dtype)
        outs0 = jnp.zeros((M,) + mb_shape, xs.dtype)
        (_, outs), _ = jax.lax.scan(tick, (buf0, outs0), jnp.arange(ticks))
        # only the LAST stage's record is meaningful; broadcast it to all
        # pipe shards (out_specs treats the pipe axis as replicated)
        outs = jax.lax.psum(
            jnp.where(stage_id == S - 1, outs, jnp.zeros_like(outs)), "pipe")
        return outs

    fn = jax.shard_map(
        per_stage, mesh=mesh,
        in_specs=(P("pipe"), P(None, "data")),
        out_specs=P(None, "data"),
        check_vma=False,
    )
    return fn(stage_params, x)


def bubble_fraction(n_microbatches: int, stages: int) -> float:
    return (stages - 1) / (n_microbatches + stages - 1)
