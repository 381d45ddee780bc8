"""Reference BERT encoder with its masked-LM loss, as the configuration
file states it (bench/configs/bert_large.json lists where that departs from
arXiv:1810.04805): pre-norm blocks, LayerNorm eps 1e-6, sinusoidal absolute
positions plus rotary q/k, tanh-approximated GELU, no token types or pooler,
and an output projection over the padded vocabulary with no transform
layer. Bidirectional attention; labels only at masked positions."""
from __future__ import annotations

import jax

from reference.common import (attention, cross_entropy, einsum, layernorm,
                    scan_layers, sinusoidal)

EPS = 1e-6


def loss(weights, batch, model, mode="fp32"):
    """Masked-LM loss of one micro-batch; `model` is the configuration
    file's `model` block."""
    theta = model.get("rope_theta", 10000.0)

    def block(x, p):
        a = layernorm(x, p["attn_norm_scale"], p["attn_norm_bias"], EPS)
        x = x + attention(a, p, causal=False, theta=theta, mode=mode)
        h = layernorm(x, p["mlp_norm_scale"], p["mlp_norm_bias"], EPS)
        h = jax.nn.gelu(einsum("bsd,df->bsf", h, p["w_up"], mode),
                        approximate=True)
        return x + einsum("bsf,fd->bsd", h, p["w_down"], mode)

    tokens = batch["tokens"]
    x = weights["embed"][tokens]
    x = x + sinusoidal(tokens.shape[1], x.shape[-1])
    x = scan_layers(block, weights["blocks"], x)
    x = layernorm(x, weights["final_norm_scale"], weights["final_norm_bias"],
                  EPS)
    logits = einsum("bsd,dv->bsv", x, weights["lm_head"], mode)
    return cross_entropy(logits, batch["labels"])
