"""Attention engine benchmark: full vs chunked vs quantized flash.

Times the four attention realizations the serve path dispatches between
(``kernels.ops.ATTN_ENGINES``) over prefill lengths, causal and
sliding-window:

  ``full``       materialized S^2 logits (``attn_full``) — capped at
                 S <= 8192 (a 32k logits tensor is ~17 GB);
  ``chunked``    pure-JAX online-softmax scan, with and without the
                 masked-chunk skip (``skip_ratio`` is the causal ~2x win);
  ``flash``      quantized flash kernel (``kernels.attn_flash``):
                 nibble-split int8 level dots + rowsum zero-point
                 correction, online softmax in the epilogue.
                 ``flash_vs_chunked_noskip`` is the headline ratio vs the
                 pre-skip serve dataflow this PR replaced;
                 ``flash_vs_chunked`` tracks the (smaller) remaining edge
                 over this PR's own skip-enabled chunked scan.

Emits ``name,us_per_call,derived`` CSV plus ``results/bench_attn.json``::

    PYTHONPATH=src python benchmarks/bench_attn.py [--fast]

or via ``benchmarks/run.py`` (job name ``attn_flash``).
"""
from __future__ import annotations

import json
import os
import time

import jax
import jax.numpy as jnp

FULL_MAX_S = 8192  # beyond this the S^2 logits tensor stops fitting


def _timeit(fn, *args, n: int = 3) -> float:
    out = fn(*args)
    jax.block_until_ready(out)  # warmup/compile
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n * 1e6  # us


def _case_rows(S: int, *, heads: int, hd: int, window, n: int):
    from repro.models.layers import attn_chunked, attn_full
    from repro.kernels.attn_flash import attn_flash_xla

    ks = jax.random.split(jax.random.PRNGKey(S), 3)
    q = jax.random.normal(ks[0], (1, S, heads, hd), jnp.float32)
    k = jax.random.normal(ks[1], (1, S, heads, hd), jnp.float32)
    v = jax.random.normal(ks[2], (1, S, heads, hd), jnp.float32)
    pos = jnp.arange(S)
    tag = f"S{S}" + (f"_w{window}" if window else "_causal")
    common = dict(causal=True, window=window, q_pos=pos, kv_pos=pos)

    full = jax.jit(lambda q, k, v: attn_full(q, k, v, **common))
    chunk = jax.jit(lambda q, k, v: attn_chunked(q, k, v, **common))
    dense = jax.jit(lambda q, k, v: attn_chunked(q, k, v, skip_masked=False,
                                                 **common))
    flash = jax.jit(lambda q, k, v: attn_flash_xla(q, k, v, causal=True,
                                                   window=window))

    row = dict(name=f"attn_{tag}", seq=S, heads=heads, head_dim=hd,
               window=window or 0)
    if S <= FULL_MAX_S:
        row["full_us"] = round(_timeit(full, q, k, v, n=n))
    chunk_us = _timeit(chunk, q, k, v, n=n)
    dense_us = _timeit(dense, q, k, v, n=n)
    flash_us = _timeit(flash, q, k, v, n=n)
    row.update(
        chunked_us=round(chunk_us), chunked_noskip_us=round(dense_us),
        flash_us=round(flash_us),
        skip_ratio=round(dense_us / chunk_us, 2),
        # vs this PR's skip-enabled chunked, and vs the pre-PR serve
        # dataflow (no masked-chunk skip) — the incumbent flash replaced
        flash_vs_chunked=round(chunk_us / flash_us, 2),
        flash_vs_chunked_noskip=round(dense_us / flash_us, 2))
    return row


def attn_rows(fast: bool = False):
    # smoke-model attention geometry (head_dim matches the smoke LMs).
    # The CPU flash win comes from interior kv blocks skipping the mask
    # arithmetic entirely (boundary blocks alone pay for it), so it is
    # largest where the S^2 mask/softmax chain is a big fraction of the
    # work — exactly the small-head smoke regime this gate runs in.  At
    # fatter heads the ratio compresses on CPU; the Pallas realization's
    # int8 MXU dots are the production (TPU) story.
    n = 2 if fast else 3
    lengths = (512, 2048) if fast else (512, 2048, 8192, 32768)
    rows = []
    for S in lengths:
        rows.append(_case_rows(S, heads=4, hd=32, window=None, n=n))
        rows.append(_case_rows(S, heads=4, hd=32, window=min(1024, S // 2),
                               n=n))
    os.makedirs("results", exist_ok=True)
    with open("results/bench_attn.json", "w") as f:
        json.dump(rows, f, indent=1, default=str)
    return rows


def main():
    import sys

    from repro.launch.jit_cache import enable_compile_cache

    enable_compile_cache()
    fast = "--fast" in sys.argv
    print("name,us_per_call,derived")
    for r in attn_rows(fast=fast):
        extra = {k: v for k, v in r.items() if k != "name"}
        print(f"{r['name']},{r['flash_us']},{json.dumps(extra)}")
    print("# full rows -> results/bench_attn.json", file=sys.stderr)


if __name__ == "__main__":
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
    main()
