"""Model operations of a dense decoder LM step, from the configuration.

Per token with context c, with tied embeddings:

  projections  2 * L * (d * (H + 2 Hkv) * hd + H * hd * d + 3 * d * ff)
               (the non-embedding weights; int8 activation levels)
  attention    4 * L * c * H * hd   (QK on int8 levels, PV in bfloat16)
  logits       2 * d * V            (bfloat16)

with d = hidden_size (the logits' contraction).

Time at peak sums each part over its own peak.
"""
from __future__ import annotations


def projection_weights(model: dict) -> int:
    """Weights of the quantized projections (norm scales left out)."""
    d, ff, L = (model["hidden_size"], model["intermediate_size"],
                model["num_hidden_layers"])
    H, Hkv, hd = (model["num_attention_heads"], model["num_key_value_heads"],
                  model["head_dim"])
    return L * (d * (H + 2 * Hkv) * hd + H * hd * d + 3 * d * ff)


def token_time_at_peak(model: dict, ctx: int, peaks: dict) -> float:
    L, H, hd = (model["num_hidden_layers"], model["num_attention_heads"],
                model["head_dim"])
    proj = 2.0 * projection_weights(model)
    attn = 2.0 * L * ctx * H * hd
    logits = 2.0 * model["hidden_size"] * model["vocab_size"]
    return ((proj + attn) / peaks["int8_ops"]
            + (attn + logits) / peaks["bf16_flops"])
