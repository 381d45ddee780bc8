"""Model assembly: parameter init, training forward pass, loss.

Layers are stored STACKED (leading L axis) and applied with `lax.scan`
so the HLO contains each block once regardless of depth — essential for
compiling 60-layer configs quickly and for the AdamA layer-wise backward
(core/accumulation.py reverse-scans the same stack).

Param tree layout:
  {"embed": (V_pad, D),
   "blocks":  {leaf: (L, ...)},        # main decoder stack
   "dense_blocks": {...}|absent,       # MoE dense-prefix stack
   "enc_blocks": {...}|absent,         # whisper encoder stack
   "final_norm*": (D,), "lm_head": (D, V_pad)}
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.configs.base import MODEL_SCOPE, ModelConfig
from repro.models import modules as md

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def _norm_params(cfg, d, prefix=""):
    p = {prefix + "scale": jnp.ones((d,), jnp.float32)}
    if cfg.norm == "layernorm":
        p[prefix + "bias"] = jnp.zeros((d,), jnp.float32)
    return p


def _dense(key, shape, scale=0.02):
    return (scale * jax.random.normal(key, shape, jnp.float32))


def _attn_params(cfg, key, *, cross=False, tp=1):
    d, h, kv = cfg.d_model, cfg.n_heads, cfg.n_kv_heads
    hd = cfg.resolved_head_dim
    ks = jax.random.split(key, 8)
    out_scale = 0.02 / math.sqrt(2 * cfg.num_layers)
    if cfg.attention == "mla" and not cross:
        dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
        dv = cfg.resolved_v_head_dim
        r = cfg.kv_lora_rank
        hp = cfg.padded_q_heads(tp)      # zero-padded inert heads (TP align)
        def padh(w, axis):
            if hp == h:
                return w
            pad = [(0, 0)] * w.ndim
            pad[axis] = (0, hp - h)
            return jnp.pad(w, pad)
        p = {
            "wkv_a": _dense(ks[0], (d, r + dr)),
            "kv_norm": jnp.ones((r,), jnp.float32),
            "wkv_b": padh(_dense(ks[1], (r, h, dn + dv)), 1),
            "wo": padh(_dense(ks[2], (h, dv, d), out_scale), 0),
        }
        if cfg.q_lora_rank:
            p["wq_a"] = _dense(ks[3], (d, cfg.q_lora_rank))
            p["q_norm"] = jnp.ones((cfg.q_lora_rank,), jnp.float32)
            p["wq_b"] = padh(_dense(ks[4], (cfg.q_lora_rank, h, dn + dr)), 1)
        else:
            p["wq"] = padh(_dense(ks[3], (d, h, dn + dr)), 1)
        return p
    sfx = "_x" if cross else ""
    return {
        f"wq{sfx}": _dense(ks[0], (d, h, hd)),
        f"wk{sfx}": _dense(ks[1], (d, kv, hd)),
        f"wv{sfx}": _dense(ks[2], (d, kv, hd)),
        f"wo{sfx}": _dense(ks[3], (h, hd, d), out_scale),
    }


def _mlp_params(cfg, key, d_ff=None):
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    out_scale = 0.02 / math.sqrt(2 * cfg.num_layers)
    if cfg.act == "silu":
        return {"w_gate": _dense(ks[0], (d, f)), "w_up": _dense(ks[1], (d, f)),
                "w_down": _dense(ks[2], (f, d), out_scale)}
    return {"w_up": _dense(ks[0], (d, f)),
            "w_down": _dense(ks[1], (f, d), out_scale)}


def _moe_params(cfg, key):
    mc = cfg.moe
    d, e, f = cfg.d_model, mc.n_experts, mc.d_expert
    ks = jax.random.split(key, 7)
    out_scale = 0.02 / math.sqrt(2 * cfg.num_layers)
    p = {
        "router": _dense(ks[0], (d, e)),
        "w_gate_e": _dense(ks[1], (e, d, f)),
        "w_up_e": _dense(ks[2], (e, d, f)),
        "w_down_e": _dense(ks[3], (e, f, d), out_scale),
    }
    if mc.n_shared:
        fs = f * mc.n_shared
        p["w_gate_s"] = _dense(ks[4], (d, fs))
        p["w_up_s"] = _dense(ks[5], (d, fs))
        p["w_down_s"] = _dense(ks[6], (fs, d), out_scale)
    return p


def _rwkv_block_params(cfg, key):
    d = cfg.d_model
    lora = 32
    ks = jax.random.split(key, 12)
    p = {}
    for i, nm in enumerate(["r", "k", "v", "g", "w"]):
        p[f"mu_{nm}"] = jnp.full((d,), 0.5, jnp.float32)
    p["w_r"] = _dense(ks[0], (d, d))
    p["w_k"] = _dense(ks[1], (d, d))
    p["w_v"] = _dense(ks[2], (d, d))
    p["w_g"] = _dense(ks[3], (d, d))
    p["w_o"] = _dense(ks[4], (d, d), 0.02 / math.sqrt(2 * cfg.num_layers))
    p["w_dd_a"] = _dense(ks[5], (d, lora))
    p["w_dd_b"] = _dense(ks[6], (lora, d))
    # w_base such that decay exp(-exp(w_base)) spans (slow..fast) per channel
    p["w_base"] = jnp.linspace(-6.0, 1.0, d, dtype=jnp.float32)
    p["u_bonus"] = _dense(ks[7], (d,), 0.5)
    p["ln_x"] = jnp.ones((d,), jnp.float32)
    p["mu_ck"] = jnp.full((d,), 0.5, jnp.float32)
    p["mu_cr"] = jnp.full((d,), 0.5, jnp.float32)
    p["w_ck"] = _dense(ks[8], (d, cfg.d_ff))
    p["w_cv"] = _dense(ks[9], (cfg.d_ff, d), 0.02 / math.sqrt(2 * cfg.num_layers))
    p["w_cr"] = _dense(ks[10], (d, d))
    p.update(_norm_params(cfg, d, "att_norm_"))
    p.update(_norm_params(cfg, d, "ffn_norm_"))
    return p


def _mamba_params(cfg, key):
    d = cfg.d_model
    sc = cfg.ssm
    di = sc.expand * d
    n = sc.d_state
    dt_rank = max(1, d // 16)
    ks = jax.random.split(key, 8)
    a = jnp.tile(jnp.arange(1, n + 1, dtype=jnp.float32)[None], (di, 1))
    return {
        "w_in": _dense(ks[0], (d, 2 * di)),
        "conv_w": _dense(ks[1], (sc.d_conv, di), 0.2),
        "conv_b": jnp.zeros((di,), jnp.float32),
        "w_dt_a": _dense(ks[2], (di, dt_rank)),
        "w_dt_b": _dense(ks[3], (dt_rank, di)),
        "dt_bias": jnp.full((di,), -4.6, jnp.float32),   # softplus^-1(0.01)
        "w_B": _dense(ks[4], (di, n)),
        "w_C": _dense(ks[5], (di, n)),
        "A_log": jnp.log(a),
        "D_skip": jnp.ones((di,), jnp.float32),
        "w_out": _dense(ks[6], (di, d), 0.02 / math.sqrt(2 * cfg.num_layers)),
    }


def _block_params(cfg, key, *, kind, tp=1):
    """kind: dense | moe | rwkv | hybrid | enc | dec."""
    if kind == "rwkv":
        return _rwkv_block_params(cfg, key)
    ks = jax.random.split(key, 4)
    p = {}
    p.update(_norm_params(cfg, cfg.d_model, "attn_norm_"))
    p.update(_norm_params(cfg, cfg.d_model, "mlp_norm_"))
    p.update(_attn_params(cfg, ks[0], tp=tp))
    if kind == "moe":
        p.update(_moe_params(cfg, ks[1]))
    else:
        p.update(_mlp_params(cfg, ks[1]))
    if kind == "hybrid":
        p.update(_mamba_params(cfg, ks[2]))
        p["fuse_norm_a"] = jnp.ones((cfg.d_model,), jnp.float32)
        p["fuse_norm_m"] = jnp.ones((cfg.d_model,), jnp.float32)
    if kind == "dec":
        p.update(_attn_params(cfg, ks[2], cross=True))
        p.update(_norm_params(cfg, cfg.d_model, "cross_norm_"))
    return p


def _stack(cfg, key, n, *, kind, tp=1):
    keys = jax.random.split(key, n)
    return jax.vmap(lambda k: _block_params(cfg, k, kind=kind, tp=tp))(keys)


def main_stack_kind(cfg) -> str:
    return {"dense": "dense", "encoder": "dense", "vlm": "dense",
            "moe": "moe", "ssm": "rwkv", "hybrid": "hybrid",
            "audio": "dec"}[cfg.arch_type]


def n_main_layers(cfg) -> int:
    if cfg.moe is not None:
        return cfg.num_layers - cfg.moe.dense_prefix
    return cfg.num_layers


def init_params(cfg: ModelConfig, key, tp: int = 1) -> Params:
    vp = cfg.padded_vocab(tp)
    ks = jax.random.split(key, 6)
    params: Params = {
        "embed": _dense(ks[0], (vp, cfg.d_model)),
        "lm_head": _dense(ks[1], (cfg.d_model, vp)),
    }
    params.update(_norm_params(cfg, cfg.d_model, "final_norm_"))
    kind = main_stack_kind(cfg)
    params["blocks"] = _stack(cfg, ks[2], n_main_layers(cfg), kind=kind, tp=tp)
    if cfg.moe is not None and cfg.moe.dense_prefix:
        params["dense_blocks"] = _stack(cfg, ks[3], cfg.moe.dense_prefix,
                                        kind="dense", tp=tp)
    if cfg.encoder_layers:
        params["enc_blocks"] = _stack(cfg, ks[4], cfg.encoder_layers,
                                      kind="dense", tp=tp)
        params.update(_norm_params(cfg, cfg.d_model, "enc_norm_"))
    return params


def abstract_params(cfg: ModelConfig, tp: int = 1) -> Params:
    """Shape-only param tree (no allocation) for the dry-run."""
    return jax.eval_shape(lambda: init_params(cfg, jax.random.key(0), tp))


def count_params_analytic(cfg: ModelConfig, active_only: bool = False) -> int:
    tree = abstract_params(cfg, tp=1)
    total = 0
    frac = 1.0
    if active_only and cfg.moe is not None:
        frac = cfg.moe.top_k / cfg.moe.n_experts
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        name = jax.tree_util.keystr(path)
        size = int(np.prod(leaf.shape))
        if active_only and "_e'" in name:        # routed expert weights
            size = int(size * frac)
        total += size
    return total


# ---------------------------------------------------------------------------
# Block application (training / full-sequence)
# ---------------------------------------------------------------------------


def apply_block(cfg, p, x, positions, *, kind, causal=True, enc_kv=None):
    """One transformer block on (B,S,D). Returns (x, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    if kind == "rwkv":
        b, _, d = x.shape
        hd = cfg.ssm.head_dim
        h = d // hd
        zeros_x = jnp.zeros((b, d), x.dtype)
        st = jnp.zeros((b, h, hd, hd), jnp.float32)
        a_in = md.apply_norm(cfg, p, x, "att_norm_")
        y, _, _ = md.rwkv6_timemix(cfg, p, a_in, zeros_x, st)
        x = x + y
        c_in = md.apply_norm(cfg, p, x, "ffn_norm_")
        y, _ = md.rwkv6_channelmix(p, c_in, zeros_x)
        return x + y, aux

    a_in = md.apply_norm(cfg, p, x, "attn_norm_")
    if cfg.attention == "mla":
        attn = md.mla_attention(cfg, p, a_in, positions, causal=causal)
    else:
        attn = md.gqa_attention(cfg, p, a_in, positions, causal=causal)
    if kind == "hybrid":
        mam, _, _ = md.mamba_mix(cfg, p, a_in)
        attn = 0.5 * (md.rmsnorm(attn, p["fuse_norm_a"]) +
                      md.rmsnorm(mam, p["fuse_norm_m"]))
    x = x + attn
    if kind == "dec":
        c_in = md.apply_norm(cfg, p, x, "cross_norm_")
        x = x + md.cross_attention(cfg, p, c_in, enc_kv, positions)
    m_in = md.apply_norm(cfg, p, x, "mlp_norm_")
    if kind == "moe":
        y, aux = md.moe_ffn(cfg, p, m_in)
    else:
        y = md.mlp(cfg, p, m_in)
    return x + y, aux


def scan_blocks(cfg, stack, x, positions, *, kind, causal=True, enc_kv=None,
                remat=False):
    from repro.sharding.ctx import maybe_shard

    def body(carry, layer_p):
        h, aux = carry
        h, a = apply_block(cfg, layer_p, h, positions, kind=kind,
                           causal=causal, enc_kv=enc_kv)
        # layer-boundary activation sharding (MaxText-style): the scan
        # carry is what autodiff saves per layer — shard it over BOTH mesh
        # axes (batch x d_model) or the residual stack occupies
        # L*B*S*D/16 instead of /256 per device.
        h = maybe_shard(h, "dp", None, "model")
        return (h, aux + a), None

    if remat:
        body = jax.checkpoint(body)
    (x, aux), _ = lax.scan(body, (x, jnp.zeros((), jnp.float32)), stack)
    return x, aux


# ---------------------------------------------------------------------------
# Full forward + loss
# ---------------------------------------------------------------------------


def embed_tokens(cfg, params, tokens, positions):
    """Token embedding. Under an installed mesh (sharded vocab) the lookup is
    a one-hot contraction: a gather over a tensor-parallel vocab axis makes
    XLA SPMD rematerialize the whole table (observed 185 GiB/step); the
    one-hot matmul keeps every shard local and reduces with one small psum.
    Costs 2*B*S*V*D MAC flops (~4% of a training step) — the standard TPU
    trade."""
    from repro.sharding import ctx
    table = params["embed"].astype(_cdt(cfg))
    if ctx._MESH.get() is not None:
        onehot = (tokens[..., None] ==
                  jnp.arange(table.shape[0], dtype=jnp.int32)).astype(table.dtype)
        x = jnp.einsum("bsv,vd->bsd", onehot, table)
        x = ctx.maybe_shard(x, "dp", None, None)
    else:
        x = table[tokens]
    if cfg.pos_emb == "sinusoidal":
        x = x + md.sinusoidal_positions(positions, cfg.d_model).astype(x.dtype)
    return x


def _cdt(cfg):
    return jnp.dtype(cfg.compute_dtype)


def forward(cfg: ModelConfig, params: Params, batch: Dict[str, jnp.ndarray],
            *, remat: bool = False):
    """Training/prefill forward. Returns (logits fp32 (B,S,Vp), aux_loss)."""
    tokens = batch["tokens"]
    b, s = tokens.shape
    causal = cfg.arch_type != "encoder"
    aux = jnp.zeros((), jnp.float32)
    enc_kv = None

    if cfg.arch_type == "audio":
        frames = batch["frames"].astype(_cdt(cfg))       # stub embeddings
        se = frames.shape[1]
        epos = jnp.broadcast_to(jnp.arange(se, dtype=jnp.int32), (b, se))
        e = frames + md.sinusoidal_positions(epos, cfg.d_model).astype(frames.dtype)
        e, aux_e = scan_blocks(cfg, params["enc_blocks"], e, epos,
                               kind="dense", causal=False, remat=remat)
        aux = aux + aux_e
        enc_out = md.apply_norm(cfg, params, e, "enc_norm_")
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        x = embed_tokens(cfg, params, tokens, positions)
        # cross k/v are per-layer projections; computed inside scan via params
        x, aux_d = _scan_dec(cfg, params["blocks"], x, positions, enc_out,
                             remat=remat)
        aux = aux + aux_d
    elif cfg.arch_type == "vlm":
        patches = batch["patches"].astype(_cdt(cfg))     # stub embeddings
        np_ = patches.shape[1]
        positions = jnp.broadcast_to(
            jnp.arange(np_ + s, dtype=jnp.int32), (b, np_ + s))
        xt = embed_tokens(cfg, params, tokens, positions[:, np_:])
        x = jnp.concatenate([patches, xt], axis=1)
        x, aux = scan_blocks(cfg, params["blocks"], x, positions,
                             kind="dense", causal=True, remat=remat)
        positions = positions  # logits computed on text tail below
    else:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        x = embed_tokens(cfg, params, tokens, positions)
        if "dense_blocks" in params:
            x, a0 = scan_blocks(cfg, params["dense_blocks"], x, positions,
                                kind="dense", causal=causal, remat=remat)
            aux = aux + a0
        x, a1 = scan_blocks(cfg, params["blocks"], x, positions,
                            kind=main_stack_kind(cfg), causal=causal,
                            remat=remat)
        aux = aux + a1

    if cfg.arch_type == "vlm":
        x = x[:, -s:]                                    # text tail only
    x = md.apply_norm(cfg, params, x, "final_norm_")
    logits = (x @ params["lm_head"].astype(x.dtype)).astype(jnp.float32)
    return logits, aux


def _scan_dec(cfg, stack, x, positions, enc_out, *, remat=False):
    from repro.sharding.ctx import maybe_shard

    def body(carry, layer_p):
        h, aux = carry
        enc_kv = md.encode_cross_kv(layer_p, enc_out)
        h, a = apply_block(cfg, layer_p, h, positions, kind="dec",
                           causal=True, enc_kv=enc_kv)
        h = maybe_shard(h, "dp", None, "model")
        return (h, aux + a), None
    if remat:
        body = jax.checkpoint(body)
    (x, aux), _ = lax.scan(body, (x, jnp.zeros((), jnp.float32)), stack)
    return x, aux


def cross_entropy(logits, labels):
    """logits (B,S,V) fp32; labels (B,S) int32, -1 = masked. Mean over valid.

    The gold logit is extracted with a one-hot contraction, not
    take_along_axis: a gather along a tensor-parallel-sharded vocab axis
    forces SPMD to rematerialize the full logits; the one-hot product stays
    local per shard and reduces with a cheap psum."""
    mask = labels >= 0
    safe = jnp.where(mask, labels, 0)
    lse = jax.nn.logsumexp(logits, axis=-1)
    onehot = (safe[..., None] == jnp.arange(logits.shape[-1],
                                            dtype=jnp.int32)).astype(logits.dtype)
    gold = jnp.sum(logits * onehot, axis=-1)
    nll = (lse - gold) * mask.astype(logits.dtype)
    return nll.sum() / jnp.maximum(mask.sum(), 1)


def loss_fn(cfg: ModelConfig, params: Params, batch, *, remat: bool = False):
    with jax.named_scope(MODEL_SCOPE):
        logits, aux = forward(cfg, params, batch, remat=remat)
        return cross_entropy(logits, batch["labels"]) + aux
