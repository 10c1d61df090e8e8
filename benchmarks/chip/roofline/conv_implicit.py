"""Least work of one ``conv_implicit`` call: a quantized conv layer on
int8 activation levels and int8 weight levels, f32 output.

ops   = 2 * B * Ho * Wo * k * k * cin * cout        (int8 MACs)
bytes = B * H * W * cin      (activation levels in, 1 byte each)
      + k * k * cin * cout   (weight levels, 1 byte each)
      + B * Ho * Wo * cout * 4  (f32 out)
"""
from __future__ import annotations

import re

ENGINE = "implicit"
PATTERN = re.compile(r"conv_implicit")


def ops_bytes(layer: dict, batch: int) -> tuple[float, float]:
    ops = 2.0 * batch * layer["macs"]
    nbytes = (batch * layer["h_in"] ** 2 * layer["cin"]
              + layer["k"] ** 2 * layer["cin"] * layer["cout"]
              + batch * layer["h_out"] ** 2 * layer["cout"] * 4)
    return ops, float(nbytes)


def least_time(layer: dict, batch: int, peaks: dict) -> float:
    ops, nbytes = ops_bytes(layer, batch)
    return max(ops / peaks["int8_ops"], nbytes / peaks["hbm_bytes_per_s"])


def match(name: str) -> bool:
    """The kernel's own ops: the op's name (the HLO text before " = "),
    not ops that merely take its output."""
    return bool(PATTERN.match(name.split(" = ", 1)[0].lstrip("%")))
