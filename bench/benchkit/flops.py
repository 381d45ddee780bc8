"""Operations a training step needs and bytes the arena kernels must move,
counted from the configuration and the shapes.

Required operations per token (`train_flops_per_token`): forward and
backward (three times the forward's matrix products), counted as 2 per
multiply-add, from the configuration file's `model` block (the program's
ModelConfig names):
  per layer  q, k, v, o projections      2 * D * hd * (2 * H + 2 * KV)
             scores and values            2 * 2 * H * hd * ctx
             MLP                          2 * (2 or 3) * D * F
  head                                    2 * D * V
Latent attention (`attention: "mla"`; dn, dr the no-rope and rope parts of
a query/key head, dv a value head, R the kv latent rank) takes, per layer:
  q          2 * D * H * (dn + dr), or through the q latent (`q_lora_rank`
             Rq) 2 * D * Rq + 2 * Rq * H * (dn + dr)
  kv_a       2 * D * (R + dr)
  kv_b       2 * R * H * (dn + dv)
  o          2 * H * dv * D
  scores and values  2 * H * (dn + dr + dv) * ctx
With a `moe` block the first `dense_prefix` layers keep the MLP at F and
each later layer has, in its place (E experts of width de, k per token):
  router     2 * D * E
  shared     2 * (2 or 3) * D * de * n_shared
  routed     2 * (2 or 3) * D * de * k * held / E
where `held` is the block's `n_experts_held` (the chip's share of the
experts) and E when it is absent. ctx is the sequence length for
bidirectional attention and the mean causal context (S + 1) / 2 for causal
attention. Recomputation (remat), the key/value blocks attention pads up
to its block size, the vocabulary's padding, the one-hot embedding lookup
and the norms are not work the model needs and are not counted.

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


def layer_flops(model: Dict, seq_len: int) -> Dict[str, float]:
    """Forward operations per token of one layer's parts: "proj" and
    "attn" in every layer, "mlp" in a dense layer, and "router", "shared"
    and "routed" in an expert layer (see the module's docstring)."""
    d, h = model["d_model"], model["n_heads"]
    hd = model.get("head_dim") or d // h
    ffn = 3 if model.get("act", "silu") == "silu" else 2
    causal = model.get("arch_type") != "encoder"
    ctx = (seq_len + 1) / 2 if causal else seq_len
    if model.get("attention") == "mla":
        dn, dr = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
        dv, r = model.get("v_head_dim") or hd, model["kv_lora_rank"]
        rq = model.get("q_lora_rank")
        q = (2 * d * rq + 2 * rq * h * (dn + dr) if rq
             else 2 * d * h * (dn + dr))
        out = {"proj": q + 2 * d * (r + dr) + 2 * r * h * (dn + dv)
               + 2 * h * dv * d,
               "attn": 2 * h * (dn + dr + dv) * ctx}
    else:
        kv = model.get("n_kv_heads", h)
        out = {"proj": 2 * d * hd * (2 * h + 2 * kv),
               "attn": 2 * 2 * h * hd * ctx}
    out["mlp"] = 2 * ffn * d * model["d_ff"]
    moe = model.get("moe")
    if moe:
        e, de = moe["n_experts"], moe["d_expert"]
        held = moe.get("n_experts_held", e)
        out.update(router=2 * d * e,
                   shared=2 * ffn * d * de * moe.get("n_shared", 0),
                   routed=2 * ffn * d * de * moe["top_k"] * held / e)
    return out


def train_flops_per_token(model: Dict, seq_len: int) -> float:
    """Required forward + backward operations per token; `model` is the
    configuration file's `model` block."""
    part = layer_flops(model, seq_len)
    layers = model["num_layers"]
    moe = model.get("moe")
    dense = moe.get("dense_prefix", 0) if moe else layers
    expert = part.get("router", 0) + part.get("shared", 0) \
        + part.get("routed", 0)
    forward = (layers * (part["proj"] + part["attn"]) + dense * part["mlp"]
               + (layers - dense) * expert
               + 2 * model["d_model"] * model["vocab_size"])
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
