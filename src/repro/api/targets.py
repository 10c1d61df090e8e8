"""HardwareTarget registry: one cost/dispatch abstraction per backend.

The paper's evaluation (§III-C/D) and the serving stack's engine selection
used to live in different worlds: ``pim/energy.DeviceModel`` +
``pim/mapper.accel_cost`` priced the four accelerator designs, while
``kernels/ops.cost_model_engine`` carried its own ad-hoc CPU/TPU crossover
constants.  This module unifies both behind one interface:

    target = get_target("sot_mram")          # or cpu / tpu / imce / ...
    cost   = target.cost(geom, a_bits, w_bits)   # Cost(energy_pj, cycles,
                                                 #      bytes_moved)

Two target families:

* :class:`ComputeTarget` (``cpu``, ``tpu``) — real serve backends.  Their
  *cost tables* are exactly the crossover constants the engine heuristic
  used to hard-code (``IMPLICIT_*`` in ``kernels/ops``); ``select_engine``
  is the same decision procedure, now owned by the target, and
  ``kernels/ops.cost_model_engine`` delegates here.  ``cost()`` is a
  roofline estimate (flops vs bytes) used to annotate compiled plans with
  per-layer energy/latency.
* :class:`PIMTarget` (``sot_mram``, ``imce``, ``reram``, ``cmos_asic``) —
  the paper's accelerators.  ``cost()`` prices one layer with the
  calibrated :class:`repro.pim.energy.DeviceModel`; ``report()`` prices a
  whole model bit-identically to the pre-registry ``pim/accelsim``
  pipeline (same ``accel_cost`` arithmetic, same fitted energy scale).

The registry is open: ``register_target`` adds new backends (the hook
every future scenario — new accelerators, energy-aware scheduling,
per-target intermittency budgets — plugs into).
"""
from __future__ import annotations

import dataclasses
from typing import Sequence

from repro.pim.energy import (CLOCK_GHZ, DESIGNS, SUBARRAY_COLS,
                              TABLE2_AREA_MM2, TABLE2_ENERGY_SCALE,
                              DeviceModel)
from repro.pim.mapper import LayerWork, accel_cost


@dataclasses.dataclass(frozen=True)
class Cost:
    """One layer's (or model's) cost on one target."""

    energy_pj: float
    cycles: float
    bytes_moved: float

    def __add__(self, other: "Cost") -> "Cost":
        return Cost(self.energy_pj + other.energy_pj,
                    self.cycles + other.cycles,
                    self.bytes_moved + other.bytes_moved)


@dataclasses.dataclass(frozen=True)
class LayerGeometry:
    """The GEMM view of one layer: (m, k) x (k, n).

    For a conv layer m = out_h*out_w (per image), k = kh*kw*cin, n = cout;
    MACs = m*k*n.  Every target costs this view — the conv-specific
    eligibility bounds (``ConvShape``) stay on the dispatch side.
    """

    m: int
    k: int
    n: int

    @property
    def macs(self) -> int:
        return self.m * self.k * self.n


class HardwareTarget:
    """Base: a named backend with a layer cost model."""

    name: str = "?"
    kind: str = "?"          # "compute" (serve backend) | "pim" (simulated)

    def cost(self, geom: LayerGeometry, a_bits: int, w_bits: int) -> Cost:
        raise NotImplementedError

    def __repr__(self) -> str:  # pragma: no cover - debug nicety
        return f"<{type(self).__name__} {self.name!r} ({self.kind})>"


# ---------------------------------------------------------------------------
# Compute targets: the serve backends (cpu / tpu)
# ---------------------------------------------------------------------------

# shared implicit-conv eligibility: the kernel supports these strides, and
# a 1x1 conv has no patch blowup (im2col is the identity there)
IMPLICIT_STRIDES = (1, 2)
IMPLICIT_AMP_MIN = 4.0
IMPLICIT_PADDINGS = ("SAME", "VALID")


def _implicit_eligible(conv) -> bool:
    return (conv is not None and conv.kh * conv.kw > 1
            and conv.stride in IMPLICIT_STRIDES
            and conv.padding in IMPLICIT_PADDINGS
            # no blowup, nothing to save: full-window FC-as-conv layers
            # (oh=ow=1, amplification 1) stay on the dense fused GEMM
            and conv.read_amplification >= IMPLICIT_AMP_MIN)


@dataclasses.dataclass(frozen=True)
class ComputeTarget(HardwareTarget):
    """A real serve backend: engine dispatch table + roofline cost model.

    ``table`` holds every crossover constant ``select_engine`` consults —
    the numbers measured in ``benchmarks/bench_conv.py`` — so a dispatch
    retune is a target edit, not a heuristic rewrite.  The per-op physical
    constants are order-of-magnitude figures for plan annotation (serving
    decisions never depend on them; the PIM models are the calibrated
    ones).
    """

    name: str = "cpu"
    kind: str = dataclasses.field(default="compute", init=False)
    table: tuple = ()               # ((constant, value), ...) cost table
    clock_ghz: float = 3.0
    flops_per_cycle: float = 32.0   # sustained fused-multiply-add lanes
    bytes_per_cycle: float = 16.0   # sustained memory-system bandwidth
    pj_per_flop: float = 2.0
    pj_per_byte: float = 20.0

    def __getitem__(self, const: str) -> float:
        return dict(self.table)[const]

    def cost(self, geom: LayerGeometry, a_bits: int, w_bits: int) -> Cost:
        """Roofline estimate: compute-bound vs bandwidth-bound cycles."""
        itemsize = 1 if max(a_bits, w_bits) <= 7 else 4
        flops = 2.0 * geom.macs
        bytes_moved = float(itemsize * (geom.m * geom.k + geom.k * geom.n)
                            + 4 * geom.m * geom.n)
        cycles = max(flops / self.flops_per_cycle,
                     bytes_moved / self.bytes_per_cycle)
        return Cost(energy_pj=flops * self.pj_per_flop
                    + bytes_moved * self.pj_per_byte,
                    cycles=cycles, bytes_moved=bytes_moved)

    def select_engine(self, m: int, k: int, n: int, a_bits: int, w_bits: int,
                      conv=None) -> str:
        raise NotImplementedError

    def attn_cost(self, attn) -> Cost:
        """Roofline estimate for one attention layer (plan annotation).

        Scores + weighted values are two GEMMs over the *effective* kv
        extent (a sliding window bounds it; causal halves it), per head
        and batch row.  Same physical constants as :meth:`cost`.
        """
        if attn.window:
            eff_kv = min(attn.window, attn.seq_kv)
        elif attn.causal and attn.seq_q == attn.seq_kv:
            eff_kv = max(attn.seq_kv // 2, 1)
        else:
            eff_kv = attn.seq_kv
        geom = LayerGeometry(m=attn.batch * attn.heads * attn.seq_q,
                             k=attn.head_dim, n=eff_kv)
        qk = self.cost(geom, 8, 8)
        return qk + qk  # P @ V moves/computes the mirror of Q @ K^T

    def select_attn_engine(self, attn) -> str:
        """Pick the attention engine for one prefill/train geometry.

        Shared decision procedure over per-target table constants
        (``attn_*``); ``attn`` is a :class:`repro.kernels.ops.AttnShape`.
        Engines, all realized in ``models/layers.py`` /
        ``kernels/attn_flash.py``:

          ``full``     materialized S^2 logits + one softmax — fastest
                       while the logits fit cache/HBM;
          ``chunked``  online-softmax scan (O(S) memory), masked kv chunks
                       skipped;
          ``banded``   block-diagonal sliding-window evaluation — only
                       defined when a window bounds the band;
          ``flash``    the quantized flash kernel — only when the serve
                       path is quantized (it consumes level-quantized q/k,
                       so it would change train/full-precision numerics);
          ``paged``    the page-table gather engine — page-table
                       geometries (``attn.page_size`` set) ALWAYS dispatch
                       it: no other engine can read a paged pool.
        """
        from repro.kernels.attn_flash import flash_levels_exact

        if getattr(attn, "page_size", None):
            return "paged"
        t = dict(self.table)
        seq = max(attn.seq_q, attn.seq_kv)
        if (attn.quantized and seq >= t["attn_flash_seq_min"]
                and attn.seq_q > 1
                and flash_levels_exact(attn.head_dim, 8, 8)):
            return "flash"
        if (attn.window and attn.banded_ok
                and attn.seq_q > 2 * attn.window):
            return "banded"
        if seq >= t["attn_chunk_seq_min"]:
            return "chunked"
        return "full"


@dataclasses.dataclass(frozen=True)
class CpuTarget(ComputeTarget):
    """CPU (and any non-TPU jax backend): XLA lowers integer matmuls to
    scalar loops, so the float unit wins while exact; the implicit direct
    conv pays off once the batched problem moves enough amplified patch
    traffic (measured crossover, ``benchmarks/bench_conv.py`` batch 1-8).
    """

    name: str = "cpu"
    table: tuple = (
        # implicit wins once conv.m * amplification crosses this, amortized
        # over the batch (floored at 8 — beyond that the conv-loop cost is
        # fully amortized and only the per-element term is left)
        ("implicit_m_amp_min", 2500),
        ("implicit_batch_amortize_cap", 8),
        # shallow-K convs (cin=3 stems) lose at every batch size: each
        # (dy, dx) tap does too little dot work to cover its slice/reshape
        ("implicit_kdim_min", 128),
        # channel-EXPANDING convs (cout > cin) write cout/cin times the
        # patch bytes they save; measured (bench_conv.json) the direct
        # sweep only recovers that above cin=96 (svhn 64->128 runs at
        # 0.63x gemm, crossover 32->64 at 0.77x; 96->256 and all
        # non-expanding deep layers still win)
        ("implicit_expand_cin_min", 96),
        # online-softmax chunking beats materialized S^2 logits once the
        # sequence spills cache (the former CHUNK_ATTN_THRESHOLD)
        ("attn_chunk_seq_min", 8192),
        # the quantized flash kernel's block sweep needs enough kv blocks
        # to amortize its online-softmax state updates
        ("attn_flash_seq_min", 4096),
    )

    def select_engine(self, m, k, n, a_bits, w_bits, conv=None) -> str:
        from repro.core.and_accum import f32dot_exact
        from repro.kernels.conv_implicit import implicit_xla_exact

        if conv is not None:
            m = conv.m  # engine bounds always see the full batched rows
        t = dict(self.table)
        if (_implicit_eligible(conv) and k >= t["implicit_kdim_min"]
                and m * conv.read_amplification
                >= t["implicit_m_amp_min"]
                / min(conv.batch, t["implicit_batch_amortize_cap"])
                and (n <= k // max(conv.kh * conv.kw, 1)  # cout <= cin
                     or k // max(conv.kh * conv.kw, 1)
                     >= t["implicit_expand_cin_min"])
                and implicit_xla_exact(k, a_bits, w_bits)):
            return "implicit"
        return "f32dot" if f32dot_exact(k, a_bits, w_bits) else "int8"


@dataclasses.dataclass(frozen=True)
class TpuTarget(ComputeTarget):
    """TPU: the fused Pallas pipeline is the default; deep-K spatial convs
    route to the implicit-GEMM sweep while one image's levels fit VMEM;
    binary huge-K skinny-output problems take the VPU popcount kernel."""

    name: str = "tpu"
    clock_ghz: float = 0.94
    flops_per_cycle: float = 512.0
    bytes_per_cycle: float = 256.0
    pj_per_flop: float = 0.3
    pj_per_byte: float = 8.0
    table: tuple = (
        # only K-axes at least this deep amortize the halo'd-tile
        # bookkeeping of the implicit kernel
        ("implicit_kdim_min", 512),
        # one image's int8 levels stay VMEM-resident per batch index; leave
        # half of ~16 MiB for weight/output tiles and the double buffers
        ("implicit_vmem_bytes", 8 << 20),
        # binary, huge-K, output tile small enough that the 128x128 MXU
        # would idle: the 32x K-compressed VPU popcount path wins
        ("faithful_mn_max", 1 << 14),
        ("faithful_kdim_min", 1 << 15),
        # attention: same decision procedure as CPU; the native Pallas
        # flash kernel amortizes earlier (MXU int8 dots from block one)
        ("attn_chunk_seq_min", 8192),
        ("attn_flash_seq_min", 2048),
    )

    def select_engine(self, m, k, n, a_bits, w_bits, conv=None) -> str:
        from repro.core.prequant import level_dtype

        import jax.numpy as jnp

        if conv is not None:
            m = conv.m
        t = dict(self.table)
        if _implicit_eligible(conv) and k >= t["implicit_kdim_min"]:
            # feasibility: one image's activation LEVELS must stay
            # VMEM-resident — int8 up to 7 activation bits, int32 at 8
            # (level_dtype), so the budget is in bytes, not elements
            cin = k // max(conv.kh * conv.kw, 1)
            lvl_bytes = jnp.zeros((), level_dtype(a_bits)).dtype.itemsize
            if (conv.padded_image_elems(cin) * lvl_bytes
                    <= t["implicit_vmem_bytes"]):
                return "implicit"
        if (a_bits == 1 and w_bits == 1 and m * n <= t["faithful_mn_max"]
                and k >= t["faithful_kdim_min"]):
            return "faithful"
        return "fused"


# ---------------------------------------------------------------------------
# PIM targets: the paper's accelerator designs
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PIMTarget(HardwareTarget):
    """One of the paper's accelerators, priced with the calibrated device
    model.  ``energy_scale`` is the single per-design constant fitted to
    the Table II ImageNet column (see ``pim/accelsim`` docstring — the
    honest-knobs policy); ``report()`` reproduces that pipeline exactly.
    """

    name: str = "sot_mram"
    kind: str = dataclasses.field(default="pim", init=False)
    device: DeviceModel = None
    energy_scale: float = 1.0
    area_mm2: float = 0.0

    def work(self, geom: LayerGeometry, a_bits: int, w_bits: int) -> LayerWork:
        """Bit products -> 512-cell row operations (paper Eq. 1 mapping)."""
        bitp = geom.macs * a_bits * w_bits
        return LayerWork(macs=geom.macs, bit_products=bitp,
                         row_ops=-(-bitp // SUBARRAY_COLS))

    def cost(self, geom: LayerGeometry, a_bits: int, w_bits: int) -> Cost:
        w = self.work(geom, a_bits, w_bits)
        d = self.device
        if d.e_mac_asic:  # CMOS ASIC path: MAC array + eDRAM traffic
            cycles = w.macs / max(d.c_macs_per_cycle, 1)
            energy = w.macs * d.e_mac_asic + cycles * d.e_static_per_cycle
        else:
            per_row = d.c_and + d.c_write + d.c_cmp + d.c_accum
            cycles = w.row_ops * per_row / max(d.n_parallel_subarrays, 1)
            energy = w.row_ops * (d.e_and_row + d.e_write_row + d.e_cmp_row
                                  + d.e_accum) + cycles * d.e_static_per_cycle
        # traffic: each row-op senses + writes back one 512-bit row
        return Cost(energy_pj=energy * self.energy_scale, cycles=cycles,
                    bytes_moved=w.row_ops * 2 * SUBARRAY_COLS / 8)

    def report(self, works: Sequence[LayerWork]) -> dict:
        """Whole-model cost, bit-identical to the legacy ``accelsim``
        pipeline: one ``accel_cost`` over the full works list (NOT a sum of
        per-layer costs — float summation order is part of the contract
        the Table II tests pin), then the fitted energy scale."""
        r = accel_cost(self.device, works)
        r["energy_uj"] *= self.energy_scale
        r["area_mm2"] = self.area_mm2
        r["fps_per_mm2"] = r["fps"] / self.area_mm2
        r["gops_per_w"] = (r["macs"] * 2e-9) / (r["energy_uj"] * 1e-6)
        r["eff_per_mm2"] = r["gops_per_w"] / self.area_mm2
        r["target"] = self.name
        return r


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_REGISTRY: dict[str, HardwareTarget] = {}

# legacy spellings (paper/accelsim design names, jax backend names)
_ALIASES = {"proposed": "sot_mram", "asic": "cmos_asic", "gpu": "cpu"}


def register_target(target: HardwareTarget) -> HardwareTarget:
    _REGISTRY[target.name] = target
    return target


def available_targets() -> tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def get_target(name: str) -> HardwareTarget:
    """Resolve a target by name (aliases: proposed->sot_mram,
    asic->cmos_asic, gpu->cpu).  Unknown names raise a ValueError that
    lists every registered target."""
    key = _ALIASES.get(name, name)
    try:
        return _REGISTRY[key]
    except KeyError:
        raise ValueError(
            f"unknown hardware target {name!r}; available targets: "
            f"{', '.join(available_targets())}") from None


def target_for_backend(backend: str) -> ComputeTarget:
    """The compute target serving a jax backend string (``gpu`` takes the
    CPU tables).  A backend with no dispatch table raises ValueError: its
    engines and crossovers were never chosen, so serving it on another
    backend's table would hide the device behind the wrong engines."""
    t = _REGISTRY.get(_ALIASES.get(backend, backend))
    if not isinstance(t, ComputeTarget):
        compute = sorted(n for n, v in _REGISTRY.items()
                         if isinstance(v, ComputeTarget))
        raise ValueError(f"no compute target for jax backend {backend!r}; "
                         f"compute targets: {', '.join(compute)}")
    return t


# Energy scale per PIM design + Table II / §III-E areas.  The values live
# in ``repro.pim.energy`` (single source of truth — the DeviceModel areas
# derive from the same dicts); these names stay as the public re-export
# spelling used by reports/accelsim.
ENERGY_SCALE = TABLE2_ENERGY_SCALE
AREA_MM2 = TABLE2_AREA_MM2

CPU = register_target(CpuTarget())
TPU = register_target(TpuTarget())
SOT_MRAM = register_target(PIMTarget(
    name="sot_mram", device=DESIGNS["proposed"],
    energy_scale=ENERGY_SCALE["proposed"], area_mm2=AREA_MM2["proposed"]))
IMCE = register_target(PIMTarget(
    name="imce", device=DESIGNS["imce"],
    energy_scale=ENERGY_SCALE["imce"], area_mm2=AREA_MM2["imce"]))
RERAM = register_target(PIMTarget(
    name="reram", device=DESIGNS["reram"],
    energy_scale=ENERGY_SCALE["reram"], area_mm2=AREA_MM2["reram"]))
CMOS_ASIC = register_target(PIMTarget(
    name="cmos_asic", device=DESIGNS["asic"],
    energy_scale=ENERGY_SCALE["asic"], area_mm2=AREA_MM2["asic"]))

PIM_CLOCK_GHZ = CLOCK_GHZ
