"""Shapes of each layer of a CNN configuration, walked from the input.

The walk follows the configuration's own rules: a spatial layer pads
SAME (``ceil(h / stride)``), an FC layer of kernel k first resizes a
larger map to k x k and then covers it (1 x 1 out), a 1 x 1 kernel
covers each pixel, a pooled layer halves the map (floor).
"""
from __future__ import annotations


def walk(config: dict) -> list[dict]:
    h = int(config["image_hw"])
    fl = config["quant"]["first_last_fp"]
    out = []
    for i, l in enumerate(config["layers"]):
        k, s = l["k"], l.get("stride", 1)
        fc = l.get("fc", False)
        if fc and k > 1 and h != k:
            h = k
        ho = 1 if fc else -(-h // s)
        out.append(dict(index=i, k=k, cin=l["cin"], cout=l["cout"],
                        h_in=h, h_out=ho, fc=fc,
                        fp=fl and l.get("role", "mid") in ("first", "last"),
                        macs=ho * ho * k * k * l["cin"] * l["cout"]))
        h = max(ho // 2, 1) if l.get("pool", False) else ho
    return out


def macs_per_image(config: dict) -> int:
    return sum(l["macs"] for l in walk(config))
