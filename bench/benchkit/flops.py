"""Operations a training step needs and bytes the arena kernels must move,
counted from the configuration and the shapes.

Required operations per token (`train_flops_per_token`): forward and
backward (three times the forward's matrix products), counted as 2 per
multiply-add:
  per layer  q, k, v, o projections      2 * 4 * D * H * hd
             MLP                          2 * (2 or 3) * D * F
             scores and values            2 * 2 * H * hd * ctx
  head                                    2 * D * V
where ctx is the sequence length for bidirectional attention and the mean
causal context (S + 1) / 2 for causal attention. Recomputation (remat), the
key/value blocks attention pads up to its block size, the vocabulary's
padding and the one-hot embedding lookup are not work the model needs and
are not counted.

Kernel bytes (`fold_bytes`, `apply_bytes`): what one call must read and
write in HBM over `rows` arena rows of 1024 lanes, from the codecs' column
widths and the gradient wire's dtype: the fold reads m, v and the gradient
slab and writes m and v; the apply reads the params, m and v and writes the
params (and the bf16 working params when it emits them).
"""
from __future__ import annotations

from typing import Dict

LANES = 1024

# bytes per arena row of each codec's row-indexed columns
_CODEC_ROW_BYTES = {"fp32": LANES * 4, "int8": LANES * 1 + 4,
                    "factored": 4, "rowcol": 4}
_WIRE_ITEMSIZE = {"fp32": 4, "bf16": 2, "fp8_e4m3": 1}


def train_flops_per_token(model: Dict, seq_len: int) -> float:
    """Required forward + backward operations per token; `model` is the
    configuration file's `model` block."""
    d, h = model["d_model"], model["n_heads"]
    hd = model.get("head_dim") or d // h
    kv = model.get("n_kv_heads", h)
    f, layers, vocab = model["d_ff"], model["num_layers"], model["vocab_size"]
    gated = model.get("act", "silu") == "silu"
    causal = model.get("arch_type") != "encoder"
    proj = 2 * d * hd * (2 * h + 2 * kv)
    mlp = 2 * (3 if gated else 2) * d * f
    ctx = (seq_len + 1) / 2 if causal else seq_len
    attn = 2 * 2 * h * hd * ctx
    forward = layers * (proj + mlp + attn) + 2 * d * vocab
    return 3.0 * forward


def fold_bytes(rows: int, m_codec: str, v_codec: str, wire: str) -> int:
    """HBM bytes one whole-arena fold call moves over `rows` rows."""
    state = _CODEC_ROW_BYTES[m_codec] + _CODEC_ROW_BYTES[v_codec]
    grad = LANES * _WIRE_ITEMSIZE[wire] + (4 if wire == "fp8_e4m3" else 0)
    return rows * (2 * state + grad)


def apply_bytes(rows: int, m_codec: str, v_codec: str,
                emit_work: bool = False) -> int:
    """HBM bytes one whole-arena apply call moves over `rows` rows."""
    state = _CODEC_ROW_BYTES[m_codec] + _CODEC_ROW_BYTES[v_codec]
    params = 2 * LANES * 4 + (LANES * 2 if emit_work else 0)
    return rows * (state + params)
