"""Plain building blocks of the reference transformers, in jax.numpy.

Independent of the program: it imports nothing from `repro`, and it takes
only weights that the benchmark made from the seed. Everything is float32;
every matrix product runs at `Precision.HIGHEST`, since a float32 product on
a TPU is otherwise computed in bfloat16 passes.

`mode="fp8"` is the control: every matrix product takes its operands, and
in the backward pass its cotangent, rounded to float8_e4m3fn with one scale
per tensor (amax mapped to 448). That is the precision one step below the
bfloat16 compute the configurations state, and the comparison that decides
`correct` has to fail it.
"""
from __future__ import annotations

import contextlib
import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0


def _q8(x):
    """Round to e4m3 with a per-tensor scale, back in float32."""
    amax = jnp.max(jnp.abs(x))
    s = jnp.where(amax > 0, E4M3_MAX / amax, 1.0)
    return (x * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s


def _exact(spec, a, b):
    return jnp.einsum(spec, a, b, precision=HIGHEST)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _einsum_fp8(spec, a, b):
    return _exact(spec, _q8(a), _q8(b))


def _einsum_fp8_fwd(spec, a, b):
    qa, qb = _q8(a), _q8(b)
    return _exact(spec, qa, qb), (qa, qb)


def _einsum_fp8_bwd(spec, res, g):
    qa, qb = res
    _, vjp = jax.vjp(functools.partial(_exact, spec), qa, qb)
    return vjp(_q8(g))


_einsum_fp8.defvjp(_einsum_fp8_fwd, _einsum_fp8_bwd)


def einsum(spec: str, a, b, mode: str):
    if mode == "fp32":
        return _exact(spec, a, b)
    if mode == "fp8":
        return _einsum_fp8(spec, a, b)
    raise ValueError(f"unknown reference mode {mode!r}")


def layernorm(x, scale, bias, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * scale + bias


def rotary(x, theta):
    """Rotary embedding over the whole head, halves paired: x (B,S,H,hd)."""
    s, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv     # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]   # (S, 1, hd/2)
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def sinusoidal(s, d):
    """Absolute sinusoidal positions (S, D): [sin | cos] halves."""
    half = d // 2
    freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half, dtype=jnp.float32)
                    / (half - 1))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], -1)


def attention(x, p, *, causal, theta, mode):
    """Multi-head self-attention with rotary q/k: x (B,S,D); p holds wq,
    wk, wv (D,H,hd) and wo (H,hd,D)."""
    q = rotary(einsum("bsd,dhk->bshk", x, p["wq"], mode), theta)
    k = rotary(einsum("bsd,dhk->bshk", x, p["wk"], mode), theta)
    v = einsum("bsd,dhk->bshk", x, p["wv"], mode)
    scores = einsum("bqhk,bshk->bhqs", q, k, mode) / math.sqrt(q.shape[-1])
    if causal:
        s = x.shape[1]
        keep = jnp.tril(jnp.ones((s, s), bool))
        scores = jnp.where(keep, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = einsum("bhqs,bshk->bqhk", probs, v, mode)
    return einsum("bqhk,hkd->bqd", out, p["wo"], mode)


def cross_entropy(logits, labels):
    """Mean over positions with a label (>= 0) of -log softmax[label]."""
    valid = labels >= 0
    safe = jnp.where(valid, labels, 0)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
    nll = jnp.where(valid, lse - gold, 0.0)
    return nll.sum() / jnp.maximum(valid.sum(), 1)


_place_layer = None


@contextlib.contextmanager
def placed_layers(place):
    """While open, `scan_layers` hands each layer's input and weights to
    `place(x, layer_weights, n_layers)`, which returns them placed, before
    the block uses them. A sharding constraint there places their
    gradients too, since its transpose constrains the cotangent
    (reference/optim.py::Spread.layer)."""
    global _place_layer
    prev, _place_layer = _place_layer, place
    try:
        yield
    finally:
        _place_layer = prev


def scan_layers(block, blocks, x):
    """Apply `block(x, layer_weights)` over the stacked layers, keeping
    only each layer's input for the backward pass."""
    place, n = _place_layer, jax.tree.leaves(blocks)[0].shape[0]

    def body(h, lp):
        if place is not None:
            h, lp = place(h, lp, n)
        return block(h, lp), None
    x, _ = jax.lax.scan(jax.checkpoint(body), x, blocks)
    return x
