#!/usr/bin/env python3
"""Compile every program a cell dispatches for a described TPU v5e, with
no chip attached (the TPU compiler refuses here what it would refuse on
the chip: tiling, VMEM, memory).

    JAX_PLATFORMS=cpu python3 benchmarks/chip/rehearse.py --workload <name>

CNN cells: the served forward at every padded batch the engine can
dispatch (``engine.batch_hints``).  LM cells: the continuous engine's
``(1, chunk)`` prefill and ``(slots, 1)`` decode steps over the whole page
pool.  Arguments are shapes only; the program's own code builds each
step, with JAX's backend query answering ``tpu`` while it traces so that
the Pallas kernels are the ones compiled.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def _abstract(tree, sharding):
    import jax

    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _compile(name: str, fn, *args) -> None:
    import jax

    t = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    mem = compiled.memory_analysis()
    kernels = compiled.as_text().count("tpu_custom_call")
    print(f"rehearse: {name}: compiled in {time.perf_counter() - t:.1f} s, "
          f"{kernels} kernel calls, temp "
          f"{getattr(mem, 'temp_size_in_bytes', 0) / 2**20:.0f} MiB, args "
          f"{getattr(mem, 'argument_size_in_bytes', 0) / 2**20:.0f} MiB",
          flush=True)


def cnn(config: dict, one_chip) -> None:
    import dataclasses

    import jax
    import jax.numpy as jnp

    from harness.common import seed32
    from reference import cnn as ref
    from repro import api
    from repro.core.plan import plan_forward
    from repro.core.quant import PAPER_CONFIGS
    from repro.models.cnn import ConvSpec

    q = config["quant"]
    quant = dataclasses.replace(PAPER_CONFIGS[q["name"]], w_bits=q["w_bits"],
                                a_bits=q["a_bits"],
                                first_last_fp=q["first_last_fp"])
    eng = config["engine"]
    hw = int(config["image_hw"])
    params = ref.init_params(config, seed32(0, 1))
    compiled = api.build([ConvSpec(**l) for l in config["layers"]], quant,
                         params=params, img_hw=hw, name=config["name"]
                         ).compile(target="tpu",
                                   batch_hints=tuple(eng["batch_hints"]))
    plan = compiled.plan
    p_abs = _abstract(plan.params, one_chip)
    for b in eng["batch_hints"]:
        x = jax.ShapeDtypeStruct((b, hw, hw, 3), jnp.float32,
                                 sharding=one_chip)
        _compile(f"{config['name']} forward batch {b}",
                 lambda p, x: plan_forward(plan, x, params=p), p_abs, x)


def lm(config: dict, one_chip) -> None:
    import dataclasses

    import jax
    import jax.numpy as jnp

    from harness.common import seed32
    from harness.lm_continuous import arch_config, program_params
    from reference import llama as ref
    from repro import api
    from repro.configs import SINGLE
    from repro.core.kv_pages import pages_needed
    from repro.launch.engine import ContinuousLMEngine
    from repro.models import transformer as T

    eng = config["engine"]
    cfg = arch_config(config)
    slots, ps = int(eng["num_slots"]), int(eng["page_size"])
    n_pages, max_seq = int(eng["num_pages"]), int(eng["max_seq"])
    params = program_params(ref.init_params(config, seed32(0, 1)))
    plan = api.build(cfg, params=params).compile(
        target="tpu", batch_hints=(slots,), prompt_len=ps).plan
    del params
    # the engine's own step function, without its device state
    e = object.__new__(ContinuousLMEngine)
    e.cfg = dataclasses.replace(cfg, quant=dataclasses.replace(
        cfg.quant, act_scale_mode="row"))
    e.plan, e.qmode, e.model_plan = SINGLE, "serve", plan
    run = e._make_run()
    table_pages = pages_needed(max_seq, ps)
    cache = jax.eval_shape(lambda: T.init_paged_cache(
        e.cfg, SINGLE, slots, n_pages, ps, table_pages))
    pools = _abstract({k: cache["attn"][k] for k in ("pk", "pv", "ppos")},
                      one_chip)
    p_abs = _abstract(plan.params, one_chip)
    n_layers = cfg.n_layers
    for b, s in ((1, ps), (slots, 1)):
        def arg(shape, dtype=jnp.int32):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

        _compile(f"{config['name']} step ({b}, {s})", run, p_abs, pools,
                 arg((n_layers, b, table_pages)), arg((b, s)), arg((b,)),
                 arg((b,)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from harness.common import REPO_ROOT, Manifest

    sys.path.insert(0, os.path.join(REPO_ROOT, "src"))
    m = Manifest.load(os.path.join(REPO_ROOT, "BENCHMARK.json"))
    config = m.config(m.cell(args.workload)["config"])
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    jax.default_backend = lambda: "tpu"
    {"cnn_serve": cnn, "lm_continuous": lm}[config["driver"]](config,
                                                              one_chip)
    return 0


if __name__ == "__main__":
    sys.exit(main())
