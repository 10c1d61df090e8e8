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

PATTERN = re.compile(r"attn_paged")


def least_time(q_rows: list, ctx: list, model: dict, peaks: dict) -> float:
    H, Hkv, hd = (model["num_attention_heads"], model["num_key_value_heads"],
                  model["head_dim"])
    qk = sum(2.0 * q * c * H * hd for q, c in zip(q_rows, ctx))
    nbytes = sum(4.0 * c * Hkv * hd + 4.0 * q * H * hd
                 for q, c in zip(q_rows, ctx))
    return max(qk / peaks["int8_ops"] + qk / peaks["bf16_flops"],
               nbytes / peaks["hbm_bytes_per_s"])


def match(name: str) -> bool:
    """The kernel's own ops: the op's name (the HLO text before " = "),
    which is the ``pallas_call``'s name, not ops that merely take its
    output."""
    return bool(PATTERN.match(name.split(" = ", 1)[0].lstrip("%")))
