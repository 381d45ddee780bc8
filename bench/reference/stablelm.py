"""Reference StableLM-2 decoder with its next-token loss, as the
configuration file states it (bench/configs/stablelm_1_6b.json lists where
that departs from hf:stabilityai/stablelm-2-1_6b): pre-norm blocks with
LayerNorm (eps 1e-6), rotary embedding over the whole head (StableLM-2
rotates 25% of it), no q/k/v bias, a SiLU-gated MLP, causal attention, an
untied output projection."""
from __future__ import annotations

import jax

from reference.common import (attention, cross_entropy, einsum, layernorm,
                    scan_layers)

EPS = 1e-6


def loss(weights, batch, model, mode="fp32"):
    """Next-token loss of one micro-batch; `model` is the configuration
    file's `model` block."""
    theta = model.get("rope_theta", 10000.0)

    def block(x, p):
        a = layernorm(x, p["attn_norm_scale"], p["attn_norm_bias"], EPS)
        x = x + attention(a, p, causal=True, theta=theta, mode=mode)
        h = layernorm(x, p["mlp_norm_scale"], p["mlp_norm_bias"], EPS)
        h = (jax.nn.silu(einsum("bsd,df->bsf", h, p["w_gate"], mode))
             * einsum("bsd,df->bsf", h, p["w_up"], mode))
        return x + einsum("bsf,fd->bsd", h, p["w_down"], mode)

    x = weights["embed"][batch["tokens"]]
    x = scan_layers(block, weights["blocks"], x)
    x = layernorm(x, weights["final_norm_scale"], weights["final_norm_bias"],
                  EPS)
    logits = einsum("bsd,dv->bsv", x, weights["lm_head"], mode)
    return cross_entropy(logits, batch["labels"])
