"""Least work of one ``attn_paged`` call (one layer of one model step)
over the live context, not the page pool.

For each live slot with q new rows and context c (positions so far):

ops   QK = 2 * q * c * H * hd   (int8 levels)
      PV = 2 * q * c * H * hd   (bfloat16)
bytes    = 2 * c * Hkv * hd * 2 (K and V, bfloat16 as the pool keeps them)
         + 2 * q * H * hd * 2   (q in, out, bfloat16)

least time = max(QK / int8 peak + PV / bf16 peak, bytes / HBM bandwidth).
"""
from __future__ import annotations

import re

# The kernel carries no name of its own in the trace (its op is a
# ``closed_call``); it is the Mosaic call whose operands open with the
# scalar-prefetched page table (B, P), the per-slot scales (B,) and the
# two zero points.
PATTERN = re.compile(r'custom_call_target="tpu_custom_call", '
                     r"operand_layout_constraints=\{s32\[\d+,\d+\]\{1,0\}, "
                     r"f32\[\d+\]\{0\}, s32\[2\]\{0\}")


def least_time(q_rows: list, ctx: list, model: dict, peaks: dict) -> float:
    H, Hkv, hd = (model["num_attention_heads"], model["num_key_value_heads"],
                  model["head_dim"])
    qk = sum(2.0 * q * c * H * hd for q, c in zip(q_rows, ctx))
    nbytes = sum(4.0 * c * Hkv * hd + 4.0 * q * H * hd
                 for q, c in zip(q_rows, ctx))
    return max(qk / peaks["int8_ops"] + qk / peaks["bf16_flops"],
               nbytes / peaks["hbm_bytes_per_s"])


def match(name: str) -> bool:
    return bool(PATTERN.search(name))
