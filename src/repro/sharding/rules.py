"""Partition rules: map every param/batch/cache leaf to a PartitionSpec.

Strategy (MaxText-style 2D sharding):
  - tensor parallel over `tp_axis` ("model"): attention heads (when the head
    counts divide), FFN hidden dim, MoE expert dim, vocab dim;
  - FSDP over `fsdp_axis` ("data"): the d_model dim of the big matrices, so
    params + optimizer states scale down with the data axis too (this is what
    lets deepseek-v2-236b fit 16 GB/chip — and is also how ZeRO-1 shards the
    AdamA states, see core/zero.py);
  - the leading L (stacked layers) dim is never sharded.

Archs whose head counts don't divide the TP axis (hymba 25H/5kv, yi kv=4,
nemo/internvl kv=8 on tp=16) fall back to replicated attention projections
(d_ff / experts / vocab still sharded) — recorded here, flagged per arch in
DESIGN.md, and a hillclimb target in EXPERIMENTS.md §Perf.
"""
from __future__ import annotations

import re
from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig


def _div(n: int, mesh, axis: Optional[str]) -> bool:
    return axis is not None and axis in mesh.shape and n % mesh.shape[axis] == 0


class Rules:
    """profile="tp2d" (default): 2D TP x FSDP sharding. profile="dp": pure
    data parallel over ALL mesh axes — params replicated, optimizer states
    ZeRO-1-sharded, batch sharded over every axis. The right choice for
    models whose p+m+v fit one chip: it trades the per-layer TP activation
    all-reduces (O(L*N*B*S*D)) for one grad/state all-reduce per step
    (O(P)) — a 10-20x collective cut on <10B models (EXPERIMENTS.md §Perf).

    profile="dp_tp": the MIXED manual-dp × auto-tp composition — the
    shard_map ZeRO-1 engine holds the dp axes manual (row-sharded states,
    bucketed reduce-scatters) while GSPMD auto-shards params/activations
    over `tp_axis` only. FSDP is disabled (the manual schedule owns the dp
    dimension of the state; double-sharding d_model over dp would fight
    it), `dp_axes()` excludes the tp axis, and batch shards over dp only.
    Gated by configs/base.py::mesh_capability. Folding tp into the manual
    dp product instead is profile="dp" on the same 2D mesh, bitwise-equal
    to flat dp.
    """

    def __init__(self, cfg: ModelConfig, mesh, *, tp_axis="model",
                 fsdp_axis: Optional[str] = "data", fsdp: bool = True,
                 profile: str = "tp2d"):
        self.cfg = cfg
        self.mesh = mesh
        self.profile = profile
        if profile == "dp":
            tp_axis = None      # params FSDP over "data" (if fsdp=True),
                                # batch over every axis, states ZeRO-1
        if profile == "dp_tp":
            fsdp = False        # dp rows belong to the manual schedule
        self.tp = tp_axis if (tp_axis and tp_axis in mesh.shape) else None
        self.fsdp = fsdp_axis if (fsdp and fsdp_axis in mesh.shape) else None
        tp_size = mesh.shape.get(self.tp, 1) if self.tp else 1
        # MLA head counts are zero-padded to a tp multiple at init
        # (ModelConfig.padded_q_heads), so they shard cleanly.
        self.shard_q_heads = cfg.padded_q_heads(tp_size) % tp_size == 0
        self.shard_kv_heads = cfg.n_kv_heads % tp_size == 0
        self.tp_size = tp_size

    # -- parameter rules ----------------------------------------------------

    def _leaf_spec(self, name: str, shape: Tuple[int, ...]) -> P:
        cfg, tp, fs = self.cfg, self.tp, self.fsdp
        stacked = name.startswith(("blocks", "dense_blocks", "enc_blocks"))
        lead = (None,) if stacked else ()
        core = shape[1:] if stacked else shape

        def spec(*entries):
            return P(*(lead + entries))

        # embed: vocab over tp, d_model over fsdp — paired with the one-hot
        # matmul lookup in model.embed_tokens (plain gather over a sharded
        # vocab axis forces SPMD full-rematerialization).
        if name == "embed":
            return P(tp if _div(shape[0], self.mesh, tp) else None,
                     fs if _div(shape[1], self.mesh, fs) else None)
        if name == "lm_head":
            return P(fs if _div(shape[0], self.mesh, fs) else None,
                     tp if _div(shape[1], self.mesh, tp) else None)

        base = re.sub(r".*/", "", name)           # leaf key
        q_ok = self.shard_q_heads
        kv_ok = self.shard_kv_heads

        # attention projections (dense & cross). Head-count fallbacks:
        # q heads TP-shardable (wq/wo over heads); kv projections fall back
        # to FSDP on d_model (small, all-gathered per use); if even q heads
        # don't divide (hymba 25H) everything falls back to FSDP.
        if base in ("wq", "wq_x"):
            d, h, hd = core
            return spec(fs if _div(d, self.mesh, fs) else None,
                        tp if q_ok else None, None)
        if base in ("wk", "wv", "wk_x", "wv_x"):
            d, h, hd = core
            return spec(fs if _div(d, self.mesh, fs) else None,
                        tp if kv_ok else None, None)
        if base in ("wo", "wo_x"):
            h, hd, d = core
            if q_ok:
                return spec(tp, None, fs if _div(d, self.mesh, fs) else None)
            if _div(hd, self.mesh, tp):      # row-parallel on the v dim
                return spec(None, tp, fs if _div(d, self.mesh, fs) else None)
            return spec(None, None, fs if _div(d, self.mesh, fs) else None)
        # MLA
        if base == "wq_a":
            return spec(fs if _div(core[0], self.mesh, fs) else None, None)
        if base == "wq_b":
            return spec(fs if (not q_ok and _div(core[0], self.mesh, fs)) else None,
                        tp if q_ok else None, None)
        if base == "wkv_a":
            return spec(fs if _div(core[0], self.mesh, fs) else None, None)
        if base == "wkv_b":
            return spec(fs if (not q_ok and _div(core[0], self.mesh, fs)) else None,
                        tp if q_ok else None, None)
        # dense FFN
        if base in ("w_gate", "w_up", "w_ck", "w_gate_s", "w_up_s"):
            d, f = core
            return spec(fs if _div(d, self.mesh, fs) else None,
                        tp if _div(f, self.mesh, tp) else None)
        if base in ("w_down", "w_cv", "w_down_s"):
            f, d = core
            return spec(tp if _div(f, self.mesh, tp) else None,
                        fs if _div(d, self.mesh, fs) else None)
        # MoE experts: expert-parallel over tp, d_model over fsdp
        if base in ("w_gate_e", "w_up_e"):
            e, d, f = core
            return spec(tp if _div(e, self.mesh, tp) else None,
                        fs if _div(d, self.mesh, fs) else None, None)
        if base == "w_down_e":
            e, f, d = core
            return spec(tp if _div(e, self.mesh, tp) else None, None,
                        fs if _div(d, self.mesh, fs) else None)
        if base == "router":
            return spec(None, None)
        # RWKV time/channel mix squares
        if base in ("w_r", "w_k", "w_v", "w_g", "w_o", "w_cr"):
            d1, d2 = core
            return spec(fs if _div(d1, self.mesh, fs) else None,
                        tp if _div(d2, self.mesh, tp) else None)
        if base in ("w_dd_a", "w_dd_b"):
            return spec(None, None)
        # Mamba
        if base == "w_in":
            d, di2 = core
            return spec(fs if _div(d, self.mesh, fs) else None,
                        tp if _div(di2, self.mesh, tp) else None)
        if base in ("conv_w",):
            return spec(None, tp if _div(core[1], self.mesh, tp) else None)
        if base in ("w_dt_a", "w_B", "w_C", "A_log"):
            return spec(tp if _div(core[0], self.mesh, tp) else None, None)
        if base == "w_dt_b":
            return spec(None, tp if _div(core[1], self.mesh, tp) else None)
        if base in ("conv_b", "dt_bias", "D_skip"):
            return spec(tp if _div(core[0], self.mesh, tp) else None)
        if base == "w_out":
            di, d = core
            return spec(tp if _div(di, self.mesh, tp) else None,
                        fs if _div(d, self.mesh, fs) else None)
        # everything else (norms, mixes, biases, u_bonus, ln_x): replicated
        return spec(*([None] * len(core)))

    def params_pspecs(self, abstract_params):
        def walk(tree, prefix):
            if isinstance(tree, dict):
                return {k: walk(v, f"{prefix}/{k}" if prefix else k)
                        for k, v in tree.items()}
            return self._leaf_spec(prefix, tree.shape)
        return walk(abstract_params, "")

    def params_shardings(self, abstract_params):
        return jax.tree.map(lambda s: NamedSharding(self.mesh, s),
                            self.params_pspecs(abstract_params))

    # -- optimizer state ----------------------------------------------------

    def opt_pspecs(self, abstract_opt, abstract_params, zero1: bool = False):
        """Optimizer state mirrors params; ZeRO-1 additionally shards over the
        data axis (core/zero.py picks the dim). The "dp" profile always
        ZeRO-1-shards the states (that's its point), over every mesh axis.

        Arena-backed states (core/arena.py) are not per-leaf shardable —
        they are ONE flat (rows, LANES) buffer per moment (plus row-indexed
        codec columns). ZeRO-1 there is a ROW-RANGE shard: every m/v leaf
        gets P(dp_axes, None), validated against the kernel block alignment
        by core/zero.py::shard_rows (falls back to replicated when the row
        count does not divide — rebuild with build_layout(n_shards=...)).

        The same P(dp_axes, None) spec serves BOTH shard_map ZeRO-1
        schedules (core/dp_shardmap.py): the spec only says "split the row
        dim over dp"; which arena rows live in device k's block is the
        schedule's contract — contiguous ranges under full-pack,
        slice-k-of-every-bucket (partition order, core/buckets.py) under
        the default bucketed schedule."""
        from repro.core.state_store import is_arena_backed, row_indexed_mask
        if is_arena_backed(abstract_opt.get("m")):
            from repro.core.zero import zero1_arena_pspec
            if zero1 or self.profile in ("dp", "dp_tp"):
                spec = zero1_arena_pspec(abstract_opt["m"].layout, self.mesh,
                                         self.dp_axes() or ("data",))
            else:
                spec = P()
            # only ROW-INDEXED columns (per the codec's declared column
            # list) row-shard; replicated codec columns stay P(). The fp32
            # master-param region "p" (OptimizerConfig.master_params), the
            # error-feedback residual "ef" (grad_dtype=fp8_e4m3), and the
            # bf16 working-param cache "wp" (work_param_cache) are all
            # row-indexed arena regions and shard exactly like the
            # moments; any other extra key (e.g. scaler scalars) stays
            # replicated.
            mask = row_indexed_mask(abstract_opt)
            return {k: P() if k == "step" else
                    (jax.tree.map(lambda _: spec, abstract_opt[k])
                     if k in ("p", "ef", "wp") else
                     jax.tree.map(lambda ri: spec if ri else P(), mask[k])
                     if k in mask else
                     jax.tree.map(lambda _: P(), abstract_opt[k]))
                    for k in abstract_opt}
        pspecs = self.params_pspecs(abstract_params)
        if self.profile in ("dp", "dp_tp"):
            zero1 = True

        def mirror(sub):
            if zero1 and self.fsdp is None:
                from repro.core.zero import _add_axis
                out = pspecs
                for ax in self.dp_axes() or ("data",):
                    if ax not in self.mesh.shape:
                        continue
                    out = jax.tree.map(
                        lambda s, p: _add_axis(s, p.shape, self.mesh, ax),
                        out, sub)
                return out
            return pspecs

        out = {}
        for k, v in abstract_opt.items():
            if k == "step":
                out[k] = P()
            elif k in ("m", "v"):
                out[k] = mirror(v)
            else:                      # adafactor/sm3 'acc' trees: replicate
                out[k] = jax.tree.map(lambda _: P(), v)
        return out

    # -- batch / cache ------------------------------------------------------

    def dp_axes(self) -> Tuple[str, ...]:
        if self.profile == "dp":
            return tuple(a for a in ("pod", "data", "model")
                         if a in self.mesh.shape)
        if self.profile == "dp_tp":
            return tuple(a for a in ("pod", "data", "model")
                         if a in self.mesh.shape and a != self.tp)
        return tuple(a for a in ("pod", "data") if a in self.mesh.shape)

    def batch_pspecs(self, abstract_batch):
        dp = self.dp_axes()
        dp_size = int(np.prod([self.mesh.shape[a] for a in dp])) if dp else 1

        def leaf(x):
            if x.ndim == 0:
                return P()
            if x.shape[0] % max(dp_size, 1) == 0 and dp:
                return P(dp, *([None] * (x.ndim - 1)))
            return P(*([None] * x.ndim))
        return jax.tree.map(leaf, abstract_batch)

    def cache_pspecs(self, abstract_cache):
        """Cache layouts (see models/decode.py): batch over dp; for the long
        seq dim prefer KV-head sharding over tp, else shard the seq dim."""
        dp = self.dp_axes()
        dp_size = int(np.prod([self.mesh.shape[a] for a in dp])) if dp else 1
        tp = self.tp

        def leaf_named(name, x):
            b_ax = dp if (dp and x.shape[1] % dp_size == 0) else None
            if name == "cache_pos":
                bo = dp if (dp and x.shape[0] % dp_size == 0) else None
                return P(bo, None)
            if name in ("k", "v", "k_p", "v_p", "ck", "cv"):   # (L,B,S,KV,hd)
                kv = x.shape[3]
                if _div(kv, self.mesh, tp):
                    return P(None, b_ax, None, tp, None)
                if _div(x.shape[2], self.mesh, tp):
                    return P(None, b_ax, tp, None, None)
                return P(None, b_ax, None, None, None)
            if name in ("latent", "k_rope", "latent_p", "k_rope_p"):
                # (L,B,S,R) — latent is shared across heads: shard seq over tp
                if _div(x.shape[2], self.mesh, tp):
                    return P(None, b_ax, tp, None)
                return P(None, b_ax, None, None)
            if name == "wkv":                                   # (L,B,H,K,V)
                if _div(x.shape[2], self.mesh, tp):
                    return P(None, b_ax, tp, None, None)
                return P(None, b_ax, None, None, None)
            if name in ("shift_a", "shift_c"):                  # (L,B,D)
                return P(None, b_ax, None)
            if name == "conv":                                  # (L,B,K-1,di)
                if _div(x.shape[3], self.mesh, tp):
                    return P(None, b_ax, None, tp)
                return P(None, b_ax, None, None)
            if name == "ssm":                                   # (L,B,di,N)
                if _div(x.shape[2], self.mesh, tp):
                    return P(None, b_ax, tp, None)
                return P(None, b_ax, None, None)
            return P(*([None] * x.ndim))
        return {k: leaf_named(k, v) for k, v in abstract_cache.items()}
