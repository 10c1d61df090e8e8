"""Layer-level conv engine benchmark: implicit-GEMM vs patch-GEMM vs seed.

Three dataflows per quantized layer of the paper's CNNs:

  ``seed``      float weights re-quantized per call, f32 im2col patches,
                hardwired int8 GEMM — the seed serve path (frozen here as
                the baseline; ``core/conv_lowering.quant_conv2d`` keeps it
                runnable);
  ``gemm``      PR-1 fused pipeline: pre-quantized weights, integer
                ``im2col_sliced`` patches, backend-dispatched qGEMM —
                patches still materialize in HBM (kh*kw x read blowup);
  ``implicit``  this PR: in-register patch extraction, zero patch bytes
                (Pallas implicit-GEMM sweep on TPU, exact direct conv
                off-TPU).

Also reports the traffic accounting the §II-A sub-array mapping is about:
``patch_bytes_gemm`` (what im2col writes+rereads) vs ``input_bytes``
(what the implicit sweep reads once) — ``patch_byte_reduction`` is their
ratio, ~kh*kw for stride-1 convs.

Emits ``name,us_per_call,derived`` CSV plus ``results/bench_conv.json``::

    PYTHONPATH=src python benchmarks/bench_conv.py [--fast]

or via ``benchmarks/run.py`` (job name ``conv_implicit``).
"""
from __future__ import annotations

import json
import os
import time

import jax


def _timeit(fn, *args, n: int = 3) -> float:
    out = fn(*args)
    jax.block_until_ready(out)  # warmup/compile
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n * 1e6  # us


def _conv_oh(s, h: int) -> int:
    from repro.core.conv_lowering import _out_hw

    pad = "VALID" if (s.fc or s.k == 1) else "SAME"
    return max(_out_hw(h, h, s.k, s.k, s.stride, pad)[0], 1)


def layer_shapes(spec, img: int):
    """Replay cnn_forward's spatial bookkeeping: input (h, w) per layer."""
    h = img
    shapes = []
    for s in spec:
        if s.fc and s.k > 1 and h != s.k:
            h = s.k
        shapes.append(h)
        h = _conv_oh(s, h)
        if s.pool:
            h //= 2
    return shapes


def _layer_rows(name, spec, img: int, batch: int, quant, n: int):
    from repro.core.conv_lowering import quant_conv2d, quant_conv2d_pre
    from repro.core.prequant import is_fp_layer, level_dtype
    from repro.kernels.ops import ConvShape, select_engine
    from repro.core.prequant import prequantize_cnn_params
    from repro.models.cnn import init_cnn

    params, _ = init_cnn(jax.random.PRNGKey(0), spec)
    serve_params = prequantize_cnn_params(params, spec, quant)
    itemsize = jax.numpy.zeros((), level_dtype(quant.a_bits)).dtype.itemsize

    rows = []
    for i, (s, h) in enumerate(zip(spec, layer_shapes(spec, img))):
        if is_fp_layer(s, quant):
            continue
        pad = "VALID" if (s.fc or s.k == 1) else "SAME"
        xi = jax.random.uniform(jax.random.PRNGKey(i), (batch, h, h, s.cin))
        p, sp = params[i], serve_params[i]
        oh = _conv_oh(s, h)
        shape = ConvShape(h, h, s.k, s.k, s.stride, pad, batch=batch)
        kdim = s.k * s.k * s.cin
        gemm_engine = select_engine(batch * oh * oh, kdim, s.cout,
                                    quant.a_bits, quant.w_bits)  # no conv geo
        auto_engine = select_engine(batch * oh * oh, kdim, s.cout,
                                    quant.a_bits, quant.w_bits, conv=shape)
        common = dict(kh=s.k, kw=s.k, stride=s.stride, padding=pad,
                      a_bits=quant.a_bits, w_bits=quant.w_bits)
        seed_us = _timeit(
            lambda: quant_conv2d(xi, p["w"], stride=s.stride, padding=pad,
                                 a_bits=quant.a_bits, w_bits=quant.w_bits,
                                 engine="int8"), n=n)
        gemm_us = _timeit(
            lambda: quant_conv2d_pre(xi, sp["w_lv"], sp["s_w"], sp["z_w"],
                                     engine=gemm_engine, **common), n=n)
        row = dict(
            name=f"{name}_L{i}", kind="layer", shape=f"{h}x{h}x{s.cin}",
            k=s.k, stride=s.stride, cout=s.cout, engine=auto_engine,
            seed_us=round(seed_us), gemm_us=round(gemm_us),
            patch_bytes_gemm=batch * oh * oh * kdim * itemsize,
            input_bytes=batch * h * h * s.cin * itemsize)
        if auto_engine == "implicit" or (
                s.k > 1 and s.stride in (1, 2)):
            impl_us = _timeit(
                lambda: quant_conv2d_pre(xi, sp["w_lv"], sp["s_w"],
                                         sp["z_w"], engine="implicit",
                                         **common), n=n)
            row.update(
                implicit_us=round(impl_us),
                patch_bytes_implicit=0,
                patch_byte_reduction=round(
                    row["patch_bytes_gemm"] / row["input_bytes"], 1),
                speedup_vs_seed=round(seed_us / impl_us, 2),
                speedup_vs_gemm=round(gemm_us / impl_us, 2))
        rows.append(row)
    return rows


def crossover_rows(fast: bool = False):
    """B>1 crossover validation for the batch-aware dispatcher (PR 3).

    The serving engine dispatches co-batched buckets, so ``select_engine``
    sees ``ConvShape.batch > 1``; these rows measure implicit vs patch-GEMM
    at batch 1/2/8 on layers straddling the single-image threshold and
    record whether the batch-scaled bound picked the faster engine.
    """
    import jax

    from repro.core.conv_lowering import quant_conv2d_pre
    from repro.core.prequant import prequantize_conv_weight
    from repro.kernels.ops import ConvShape, select_engine

    n = 2 if fast else 5
    layers = [(10, 32, 64, 3), (5, 64, 64, 3)]
    if not fast:
        layers += [(20, 32, 32, 3)]
    rows = []
    for (h, cin, cout, k) in layers:
        w = jax.random.normal(jax.random.PRNGKey(0), (k, k, cin, cout))
        w_lv, s_w, z_w = prequantize_conv_weight(w, 1)
        for batch in (1, 2, 8):
            x = jax.random.uniform(jax.random.PRNGKey(1), (batch, h, h, cin))
            common = dict(kh=k, kw=k, stride=1, padding="SAME",
                          a_bits=4, w_bits=1)
            gemm_us = _timeit(lambda: quant_conv2d_pre(
                x, w_lv, s_w, z_w, engine="f32dot", **common), n=n)
            impl_us = _timeit(lambda: quant_conv2d_pre(
                x, w_lv, s_w, z_w, engine="implicit", **common), n=n)
            shape = ConvShape(h, h, k, k, 1, "SAME", batch=batch)
            pick = select_engine(shape.m, k * k * cin, cout, 4, 1, conv=shape)
            rows.append(dict(
                name=f"crossover_{h}x{h}x{cin}_B{batch}", kind="crossover",
                batch=batch, m_amp=round(shape.m * shape.read_amplification),
                gemm_us=round(gemm_us), implicit_us=round(impl_us),
                picked=pick,
                picked_faster=bool((impl_us < gemm_us)
                                   == (pick == "implicit"))))
    return rows


def conv_rows(fast: bool = False):
    from repro.core.quant import W1A4, W1A8
    from repro.models.cnn import alexnet_spec, svhn_cnn_spec

    n = 2 if fast else 5
    rows = _layer_rows("svhn_cnn", svhn_cnn_spec(32 if fast else 64), 40,
                       2, W1A4, n)
    if not fast:
        rows += _layer_rows("alexnet", alexnet_spec(), 112, 1, W1A8, n)
    rows += crossover_rows(fast=fast)
    os.makedirs("results", exist_ok=True)
    with open("results/bench_conv.json", "w") as f:
        json.dump(rows, f, indent=1, default=str)
    return rows


def main():
    import sys

    from repro.launch.jit_cache import enable_compile_cache

    enable_compile_cache()
    fast = "--fast" in sys.argv
    print("name,us_per_call,derived")
    for r in conv_rows(fast=fast):
        us = r.get("implicit_us", r["gemm_us"])
        extra = {k: v for k, v in r.items() if k not in ("name",)}
        print(f"{r['name']},{us},{json.dumps(extra)}")
    print("# full rows -> results/bench_conv.json", file=sys.stderr)


if __name__ == "__main__":
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
    main()
