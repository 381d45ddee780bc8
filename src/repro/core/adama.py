"""AdamA — Adam Accumulation (the paper's contribution), as composable
pure-function pieces.

The mini-batch lifecycle (Algorithm 1/2):

    state = init(params)
    state = begin_minibatch(state, beta1, beta2, m_devices=M)   # m*=b1, v*=M*b2*v
    for each micro-batch i:                                     # grads released
        state = accumulate(state, grads_i, beta1, beta2)        #   right after
    state = allreduce_states(state, axis_names, M)              # DP only, Eq.7/8
    params, state = finalize(params, state, lr=..., ...)        # bias-corr apply

`accumulate` is where gradients die: m += (1-b1)*g, v += (1-b2)*g^2 — after
this the gradient buffer has no further reader, which is exactly the paper's
"release memory for g" (XLA buffer liveness performs the release).

The caller is responsible for pre-scaling gradients by 1/N (or 1/(N*M) in DP)
via the loss, matching Algorithm 1 line 6.
"""
from __future__ import annotations

from typing import Any, Dict, Sequence

import jax
import jax.numpy as jnp

from repro.configs.base import APPLY_SCOPE, FOLD_SCOPE, GRAD_PACK_SCOPE
from repro.core import arena as arena_mod
from repro.core.arena import Arena

State = Dict[str, Any]


def init(params) -> State:
    zeros = lambda p: jnp.zeros_like(p, dtype=jnp.float32)
    return {"m": jax.tree.map(zeros, params),
            "v": jax.tree.map(zeros, params),
            "step": jnp.zeros((), jnp.int32)}


def init_arena(params, codec: str = "fp32", m_codec: str = "fp32",
               n_shards: int = 1, master_params: bool = False,
               error_feedback: bool = False,
               work_param_cache: bool = False,
               tp_shards: int = 1) -> State:
    """Arena-backed state: both moments are codec-encoded arena columns
    (core/state_store.py; `codec` selects v's codec, `m_codec` m's), so each
    fold/apply is ONE kernel dispatch for every registered pair. `n_shards`
    pads the layout for ZeRO-1 row-range sharding (core/zero.py::shard_rows).

    `master_params=True` adds the fp32 MASTER-PARAM region: state["p"]
    packs `params` as a third fp32 arena alongside m and v. The apply then
    updates the master and emits bf16 working params from the same kernel
    (state_store.apply_master_state) — the standard AMP contract, with the
    round-trip exact by construction.

    `error_feedback=True` adds the fp8-wire RESIDUAL region: state["ef"] is
    a zero-initialized fp32 arena holding the quantization error each fold
    left behind, in UNSCALED gradient units (the dynamic loss scale can
    change between micro-batches, so the stored residual must not carry
    it). Row-indexed like the master region, it rides the same extra-state-
    key plumbing: ZeRO-1 row-sharded, bucket-permuted (zeros are
    permutation-invariant, so no pre-permute), checkpointed, and guard-
    predicated by the engines.

    `work_param_cache=True` adds the bf16 WORKING-PARAM cache: state["wp"]
    packs `params` as bf16; the pjit engines read each step's model params
    from it (one unpack, no re-pack of the tree) and finalize refreshes it
    with the work rows the master apply emits. Requires master_params
    (enforced by OptimizerConfig)."""
    from repro.core import state_store
    layout = arena_mod.build_layout(params, n_shards=n_shards,
                                    tp_shards=tp_shards)
    state = {"m": state_store.get_codec(m_codec, "m").init(layout),
             "v": state_store.get_codec(codec, "v").init(layout),
             "step": jnp.zeros((), jnp.int32)}
    if master_params:
        state["p"] = Arena(arena_mod.pack(params, layout), layout)
    if error_feedback:
        state["ef"] = Arena.zeros(layout)
    if work_param_cache:
        state["wp"] = Arena(arena_mod.pack(params, layout,
                                           dtype=jnp.bfloat16), layout)
    return state


def working_params(state: State):
    """Model-param tree from the bf16 working-param cache (state["wp"]):
    one unpack, leaves cast back to their recorded dtypes. The engines call
    this at step start when the cache is present, making the step's param-
    tree INPUT dead — XLA prunes it, and the pack/unpack pair the non-
    cached path pays at the jit boundary disappears."""
    wp = state["wp"]
    return arena_mod.unpack(wp.data, wp.layout)


def is_arena_state(state: State) -> bool:
    from repro.core.state_store import is_arena_backed
    return is_arena_backed(state["m"])


@jax.named_scope(FOLD_SCOPE)
def begin_minibatch(state: State, beta1: float, beta2: float,
                    m_devices: int = 1) -> State:
    """m <- b1*m ; v <- M*b2*v (Eq. 6's M*beta2 pre-scale; M=1 single device).

    The arena engines skip this pass entirely: the decay is fused into the
    first fold of the mini-batch via `accumulate(..., decay=...)`, saving a
    full state-sized read+write. This standalone form remains for the
    per-leaf path and the shard_map DP engine; on arena state it decays in
    CODEC space (for int8, c*(q*s) == q*(c*s): only the scale column is
    touched)."""
    if is_arena_state(state):
        from repro.core import state_store
        mc, vc = state_store.state_codecs(state)
        return dict(state, m=mc.scale_state(state["m"], beta1),
                    v=vc.scale_state(state["v"], m_devices * beta2),
                    step=state["step"] + 1)
    return {
        "m": jax.tree.map(lambda m: beta1 * m, state["m"]),
        "v": jax.tree.map(lambda v: (m_devices * beta2) * v, state["v"]),
        "step": state["step"] + 1,
    }


def accumulate(state: State, grads, beta1: float, beta2: float,
               use_pallas: bool = False, scale: float = 1.0,
               decay=None, grad_dtype=jnp.float32, guard=None,
               from_leaves: bool = False) -> State:
    """Fold one micro-batch's gradients into (m, v); Algorithm 2 inner loop.

    `scale` multiplies g before the fold (Alg. 1 line 6's 1/N, applied
    in-kernel on the arena path). `decay=(dm, dv)` folds the begin-minibatch
    decay into this call (pass it on the first micro-batch only).
    `grad_dtype` is the arena path's gradient WIRE dtype: bf16 packs a
    half-size slab; the fold kernel upcasts in-pass and still accumulates
    the moments in fp32.

    `guard` (arena path only; OptimizerConfig.finite_guard): True
    self-checks the packed slab, a traced bool (psum-agreed under
    shard_map) is used verbatim — either way a non-finite micro-batch is a
    BITWISE no-op fold and the return becomes (new_state, flag).

    `from_leaves` (arena path, unguarded) hands the fold the gradient
    tree's sources instead of a packed slab (arena.gather_sources): the
    fold kernel gathers each row block from the leaves and the slab is
    never written. Only the relayout copies of leaves that are not a
    bitcast of (rows, LANES) remain under the pack scope."""
    if is_arena_state(state):
        from repro.core import state_store
        layout = state["m"].layout
        with jax.named_scope(GRAD_PACK_SCOPE):
            g = (arena_mod.gather_sources(grads, layout, grad_dtype)
                 if from_leaves else
                 arena_mod.pack(grads, layout, dtype=grad_dtype))
        return state_store.fold_state(state, g, beta1=beta1, beta2=beta2,
                                      scale=scale, decay=decay,
                                      grad_dtype=grad_dtype, guard=guard)
    if guard is not None:
        raise ValueError("finite guards require the arena fold path "
                         "(OptimizerConfig arena=True use_pallas=True)")
    with jax.named_scope(FOLD_SCOPE):
        if decay is not None:
            state = {"m": jax.tree.map(lambda m: decay[0] * m, state["m"]),
                     "v": jax.tree.map(lambda v: decay[1] * v, state["v"]),
                     "step": state["step"]}
        if use_pallas:
            from repro.kernels.ops import adama_accumulate_tree
            m, v = adama_accumulate_tree(state["m"], state["v"], grads,
                                         beta1=beta1, beta2=beta2,
                                         scale=scale)
            return {"m": m, "v": v, "step": state["step"]}
        m = jax.tree.map(lambda m_, g: m_ + (1 - beta1) *
                         (g.astype(jnp.float32) * scale), state["m"], grads)
        v = jax.tree.map(lambda v_, g: v_ + (1 - beta2) *
                         jnp.square(g.astype(jnp.float32) * scale),
                         state["v"], grads)
        return {"m": m, "v": v, "step": state["step"]}


def accumulate_leaf(m, v, g, beta1: float, beta2: float, use_pallas=False):
    """Single-leaf fold (used by the layer-wise backward, Algorithm 2)."""
    if use_pallas:
        from repro.kernels.ops import adama_accumulate
        return adama_accumulate(m, v, g, beta1=beta1, beta2=beta2)
    g = g.astype(jnp.float32)
    return m + (1 - beta1) * g, v + (1 - beta2) * jnp.square(g)


def allreduce_states(state: State, axis_names: Sequence[str],
                     m_devices: int) -> State:
    """Distributed sync (Eqs. 7-8): mean(m), sum(v)/M^2 — inside shard_map.

    Codec-encoded v cannot ride this path: summing int8 codes is
    meaningless, and summing factored per-row maxima is not the max of the
    summed gradients (it can UNDERestimate v and amplify updates). The
    ZeRO-1 row-range schedule reduce-scatters the fp32 GRADIENT instead,
    which composes with every codec — use zero_stage=1."""
    from repro.core.state_store import MomentState
    for k in ("m", "v"):
        if isinstance(state[k], MomentState):
            raise TypeError(
                f"allreduce_states cannot psum {state[k].codec}-coded "
                f"{'first' if k == 'm' else 'second'} moments (the sum of "
                f"codec state is not the state of the summed moments); run "
                f"the shard_map DP engine with zero_stage=1 (row-range "
                f"ZeRO-1 reduce-scatters fp32 gradients instead of states)")
    m = jax.tree.map(lambda x: jax.lax.psum(x, axis_names) / m_devices,
                     state["m"])
    v = jax.tree.map(lambda x: jax.lax.psum(x, axis_names) / (m_devices ** 2),
                     state["v"])
    # extra keys (the fp32 master-param region "p") pass through UNsummed:
    # the master is replicated and every device applies the identical
    # post-psum update to it, so it stays replicated without a collective
    return dict(state, m=m, v=v)


@jax.named_scope(APPLY_SCOPE)
def finalize(params, state: State, *, lr, beta1: float, beta2: float,
             eps: float = 1e-8, weight_decay: float = 0.0,
             use_pallas: bool = False, guard=None):
    """Bias-correct and apply (Algorithm 1 'Update' line). `state['step']` must
    already count this mini-batch (begin_minibatch increments it).

    `guard` (arena path only; traced bool, e.g. `good > 0` after a guarded
    fold scan): when false the apply is a bitwise identity — the all-
    skipped mini-batch case, where the step counter never advanced and
    bc1/bc2 would be 0 (the resulting NaNs are discarded in-kernel)."""
    t = state["step"].astype(jnp.float32)
    bc1 = 1 - beta1 ** t
    bc2 = 1 - beta2 ** t
    if is_arena_state(state):
        from repro.core import state_store
        layout = state["m"].layout
        if state_store.has_master(state):
            # master-param apply: the fp32 truth lives in state["p"] — the
            # incoming (bf16-precision working) params are never packed,
            # and the same kernel emits the next step's working params
            work, state = state_store.apply_master_state(
                state, lr=lr, bc1=bc1, bc2=bc2, eps=eps,
                weight_decay=weight_decay, guard=guard)
            if "wp" in state:    # refresh the bf16 working-param cache
                state = dict(state, wp=state["wp"].with_data(work))
            return arena_mod.unpack(work, layout), state
        p_new = state_store.apply_state(
            arena_mod.pack(params, layout), state, lr=lr, bc1=bc1, bc2=bc2,
            eps=eps, weight_decay=weight_decay, guard=guard)
        return arena_mod.unpack(p_new, layout), state
    if guard is not None:
        raise ValueError("finite guards require the arena apply path "
                         "(OptimizerConfig arena=True use_pallas=True)")
    if use_pallas:
        from repro.kernels.ops import adam_apply_tree
        new_params = adam_apply_tree(params, state["m"], state["v"],
                                     lr=lr, bc1=bc1, bc2=bc2, eps=eps,
                                     weight_decay=weight_decay)
        return new_params, state

    def upd(p, m, v):
        mh = m / bc1
        vh = v / bc2
        u = mh / (jnp.sqrt(vh) + eps)
        if weight_decay:
            u = u + weight_decay * p.astype(jnp.float32)
        return (p.astype(jnp.float32) - lr * u).astype(p.dtype)

    return jax.tree.map(upd, params, state["m"], state["v"]), state
