"""Least work of one ``fused_qgemm`` call serving an FC layer: M = batch
rows of K = k * k * cin int8 activation levels against (K, N = cout) int8
weight levels, f32 output.

ops   = 2 * M * K * N                 (int8 MACs)
bytes = M * K + K * N + M * N * 4
"""
from __future__ import annotations

import re

ENGINE = "fused"
PATTERN = re.compile(r"fused_qgemm")


def ops_bytes(layer: dict, batch: int) -> tuple[float, float]:
    k = layer["k"] ** 2 * layer["cin"]
    n = layer["cout"]
    return (2.0 * batch * k * n, float(batch * k + k * n + batch * n * 4))


def least_time(layer: dict, batch: int, peaks: dict) -> float:
    ops, nbytes = ops_bytes(layer, batch)
    return max(ops / peaks["int8_ops"], nbytes / peaks["hbm_bytes_per_s"])


def match(name: str) -> bool:
    """The kernel's own ops: the op's name (the HLO text before " = "),
    not ops that merely take its output."""
    return bool(PATTERN.match(name.split(" = ", 1)[0].lstrip("%")))
