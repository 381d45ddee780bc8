"""Serving launcher: continuous-batching decode server over the paged KV
arena (default), plus the static prefill-then-decode path it is benchmarked
against. CPU-scale with --reduced; production shapes are proven via
launch/dryrun.py.

  PYTHONPATH=src python -m repro.launch.serve --arch rwkv6-7b --reduced \
      --prompt-len 32 --gen 16 --batch 4 [--static] [--ckpt DIR]

Continuous batching (`DecodeServer`): an admission queue feeds request
slots in a paged arena (core/kv_arena.py); each scheduler tick advances ONE
chunk of at most one request's prefill and ONE fixed-width batched decode
step over every decoding request, so short requests finish and release
their blocks while long prompts are still being prefilled. Prefill is a
lax.scan of the same single-token paged step decode uses — bitwise-equal to
feeding the prompt through decode, so chunk size is a pure scheduling knob.
The decode step is jitted ONCE at a fixed lane width with the paged buffers
DONATED: steady-state decode is allocation-free, and padded lanes point at
the arena's reserved trash slot/block.

`--ckpt` sources bf16 working params straight from a restored master arena
(train/checkpoint.py::export_working_params — state["wp"] / the apply
kernel's master.astype(bf16) emission, no repack of the param tree).
"""
from __future__ import annotations

import argparse
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.configs.base import InputShape, ModelConfig
from repro.core import kv_arena
from repro.data import make_data
from repro.launch.compile_cache import use_compile_cache
from repro.models import decode as dec
from repro.models.model import init_params


@dataclass
class Request:
    """One serving request: prompt in, `gen` greedy tokens out. Timestamps
    are perf_counter seconds; `token_times` has one entry per output token
    (the p50/p99 inter-token-latency source)."""
    rid: int
    prompt: np.ndarray              # (P,) int32
    gen: int
    out: List[int] = field(default_factory=list)
    t_submit: float = 0.0
    t_done: float = 0.0
    token_times: List[float] = field(default_factory=list)


@dataclass
class _Active:
    """Scheduler-side state of an admitted request."""
    req: Request
    slot: int
    fed: int = 0                    # prompt tokens consumed by prefill
    next_token: int = 0             # decode-phase input token
    pos: int = 0                    # absolute position of next_token
    decoding: bool = False


class DecodeServer:
    """Continuous-batching greedy decode over a paged KV arena.

    `width` is the FIXED lane count of the jitted decode step (compiled
    once; idle lanes are trash-padded, so varying load never recompiles) and
    also the admission cap. `n_blocks` sizes the shared block pool — the
    back-pressure knob: admission defers (rather than crashes) when the
    pool can't back a new request's first chunk, via OutOfBlocksError."""

    def __init__(self, cfg: ModelConfig, params, *, max_len: int,
                 width: int = 4, block: int = kv_arena.BLOCK_TOKENS,
                 n_blocks: Optional[int] = None, chunk: int = 8):
        if not cfg.supports_decode:
            raise ValueError(f"{cfg.name} is encoder-only; no decode path")
        if chunk & (chunk - 1):
            raise ValueError(f"chunk {chunk} must be a power of two (ragged "
                             f"prefill tails halve down through compiled "
                             f"chunk sizes instead of retracing)")
        self.cfg = cfg
        self.params = params
        self.width = width
        self.chunk = chunk
        self.layout = dec.paged_layout(cfg, max_reqs=width, max_len=max_len,
                                       block=block, n_blocks=n_blocks)
        self.reset()
        # one compiled step per entry point, paged buffers donated: decode
        # steady state allocates nothing
        self._step = jax.jit(
            lambda p, b, s, t, tok, pos: dec.serve_step_paged(
                cfg, self.layout, p, b, s, t, tok, pos),
            donate_argnums=(1,))
        # one jit, two traces: full chunks of `chunk` tokens + size-1
        # remainder chunks (ragged tails never force a third shape)
        self._chunk_fn = jax.jit(
            lambda p, b, s, t, tok, pos: dec.serve_prefill_chunk(
                cfg, self.layout, p, b, s, t, tok, pos),
            donate_argnums=(1,))

    def reset(self) -> None:
        """Fresh arena, allocator, and queues on the SAME compiled step
        functions — benches warm up the compile on a throwaway trace, reset,
        then time the real one."""
        self.bufs = kv_arena.init_paged(self.layout)
        self.alloc = kv_arena.BlockAllocator(self.layout)
        self.queue: deque = deque()
        self.active: Dict[int, _Active] = {}
        self.done: List[Request] = []
        self.ticks = 0
        self.decode_steps = 0
        # independent active-token accounting (NOT the allocator's own
        # counters): what the resident requests' token counts justify,
        # block-rounded. serve_bench gates alloc.peak_bytes against
        # peak_active_budget, so an allocator leak (blocks not returned on
        # release, double backing) shows up as a violation instead of
        # silently inflating both sides of the comparison.
        self.peak_active_budget = 0
        self.budget_violations = 0

    def submit(self, req: Request) -> None:
        req.t_submit = time.perf_counter()
        self.queue.append(req)

    # -- scheduler ----------------------------------------------------------

    def _admit(self) -> None:
        """Admission is SLOT-gated; token blocks back lazily as the request
        actually writes (prefill chunks / decode ensures), so admitting
        never front-loads bytes the request hasn't earned. A pool too small
        even to start anything surfaces via the run() wedge detector."""
        while self.queue and len(self.active) < self.width:
            try:
                slot = self.alloc.alloc_slot()
            except kv_arena.OutOfBlocksError:
                return
            req = self.queue.popleft()
            st = _Active(req, slot)
            if len(req.prompt) == 1:
                st.decoding, st.next_token, st.pos = True, int(req.prompt[0]), 0
            self.active[slot] = st
        return

    def _prefill_tick(self) -> None:
        """Advance the oldest prefilling request by one chunk (prompt[:-1]
        through the scanned paged step; the LAST prompt token becomes the
        first decode-step input, whose logits emit output token 0)."""
        cand = [a for a in self.active.values() if not a.decoding]
        if not cand:
            return
        a = min(cand, key=lambda s: s.req.rid)
        p = a.req.prompt
        n = min(self.chunk, (len(p) - 1) - a.fed)
        if n > 0:
            # largest power-of-two chunk that fits: a P-token prompt costs
            # popcount(P-1) chunk dispatches over at most log2(chunk)+1
            # compiled sizes, instead of P-1 single-token remainder ticks
            cs = 1 << (min(n, self.chunk).bit_length() - 1)
            try:
                self.alloc.ensure_tokens(a.slot, a.fed + cs)
            except kv_arena.OutOfBlocksError:
                return                        # stall until blocks free up
            slots = jnp.asarray([a.slot], jnp.int32)
            bt = jnp.asarray(self.alloc.block_tables[[a.slot]])
            toks = jnp.asarray(p[a.fed:a.fed + cs][None].astype(np.int32))
            _, self.bufs = self._chunk_fn(
                self.params, self.bufs, slots, bt, toks,
                jnp.full((1,), a.fed, jnp.int32))
            a.fed += cs
        if a.fed >= len(p) - 1:
            a.decoding = True
            a.next_token, a.pos = int(p[-1]), len(p) - 1

    def _decode_tick(self) -> None:
        lanes: List[_Active] = []
        for a in sorted(self.active.values(), key=lambda s: s.req.rid):
            if not a.decoding:
                continue
            try:
                self.alloc.ensure_tokens(a.slot, a.pos + 1)
            except kv_arena.OutOfBlocksError:
                continue                      # stall this lane one tick
            lanes.append(a)
        # active-token budget at the post-ensure instant (the allocator's
        # high-water mark is made of exactly these moments)
        budget = sum(
            self.alloc.blocks_for_tokens(a.fed if not a.decoding
                                         else a.pos + 1)
            for a in self.active.values()) * self.layout.block_bytes
        self.peak_active_budget = max(self.peak_active_budget, budget)
        if self.alloc.live_bytes > budget:
            self.budget_violations += 1
        if not lanes:
            return
        w = self.width
        slots = np.zeros((w,), np.int32)          # pad: trash slot 0
        toks = np.zeros((w, 1), np.int32)
        pos = np.zeros((w,), np.int32)
        for i, a in enumerate(lanes):
            slots[i], toks[i, 0], pos[i] = a.slot, a.next_token, a.pos
        bt = jnp.asarray(self.alloc.block_tables[slots])
        logits, self.bufs = self._step(
            self.params, self.bufs, jnp.asarray(slots), bt,
            jnp.asarray(toks), jnp.asarray(pos))
        nxt = np.asarray(jnp.argmax(logits, -1))  # blocks until ready
        t = time.perf_counter()
        self.decode_steps += 1
        for i, a in enumerate(lanes):
            a.req.out.append(int(nxt[i]))
            a.req.token_times.append(t)
            a.next_token, a.pos = int(nxt[i]), a.pos + 1
            if len(a.req.out) >= a.req.gen:       # finished: recycle NOW
                a.req.t_done = t
                self.done.append(a.req)
                self.alloc.release(a.slot)
                del self.active[a.slot]

    def _sig(self):
        return (len(self.queue), len(self.done),
                tuple(sorted((s, a.fed, len(a.req.out), a.decoding)
                             for s, a in self.active.items())))

    def run(self) -> List[Request]:
        """Drive ticks until the queue and every active request drain. The
        scheduler is deterministic, so a tick that changes nothing proves
        no future tick can either — that raises instead of spinning."""
        while self.queue or self.active:
            sig = self._sig()
            self.ticks += 1
            self._admit()
            self._prefill_tick()
            self._decode_tick()
            if self._sig() == sig:
                raise kv_arena.OutOfBlocksError(
                    f"scheduler wedged: {len(self.queue)} queued / "
                    f"{len(self.active)} active requests but no admission, "
                    f"prefill, or decode can progress — the block pool "
                    f"({self.alloc.free_blocks} free of "
                    f"{self.layout.n_blocks - 1}) is too small for the "
                    f"working set")
        out = sorted(self.done, key=lambda r: r.rid)
        self.done = []
        return out


# ---------------------------------------------------------------------------
# Static path: prefill the whole batch, decode in lockstep
# ---------------------------------------------------------------------------


def run_static(cfg: ModelConfig, params, batch, prompt_len: int, gen: int):
    """The pre-paged serving path, timing bugs fixed: the decode clock stops
    only after `jax.block_until_ready`, and the jitted step DONATES the
    cache so each step updates in place instead of allocating a fresh
    cache. Returns (tokens (B, gen+? ...), stats dict)."""
    b = batch["tokens"].shape[0]
    prefill_fn = dec.prefill_whisper if cfg.arch_type == "audio" else dec.prefill
    offset = cfg.n_patch_tokens if cfg.arch_type == "vlm" else 0
    total = prompt_len + gen + offset

    t0 = time.perf_counter()
    logits, cache = jax.jit(lambda p, bt: prefill_fn(cfg, p, bt))(params, batch)
    cache = jax.jit(lambda c: dec.grow_cache(cfg, c, total))(cache)
    jax.block_until_ready((logits, cache))
    t_prefill = time.perf_counter() - t0

    step = jax.jit(lambda p, c, t, s: dec.serve_step(cfg, p, c, t, s),
                   donate_argnums=(1,))
    tok = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
    out_tokens = [np.asarray(tok)]
    pos = jnp.full((b,), prompt_len + offset, jnp.int32)
    token_times = []
    t0 = time.perf_counter()
    for i in range(gen):
        logits, cache = step(params, cache, tok, pos + i)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
        out_tokens.append(np.asarray(tok))        # blocks until ready
        token_times.append(time.perf_counter())
    jax.block_until_ready(cache)
    dt = time.perf_counter() - t0
    tokens = np.concatenate(out_tokens, axis=1)
    return tokens, {"prefill_s": t_prefill, "decode_s": dt,
                    "tok_per_s": gen * b / dt if dt else float("inf"),
                    "token_times": token_times}


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def params_from_ckpt(cfg: ModelConfig, ckpt_dir: str, *, step=None,
                     codec: str = "fp32", m_codec: str = "fp32",
                     wp: bool = False, finite_guard: bool = False):
    """Abstract-restore a training checkpoint and export serving params
    through the master arena (no repack). The optimizer knobs must match
    the run that wrote the checkpoint (restore validates loudly)."""
    import dataclasses

    from repro.configs.base import OptimizerConfig
    from repro.core.accumulation import _arena_init
    from repro.train import checkpoint as ckpt_mod

    opt_cfg = dataclasses.replace(
        OptimizerConfig(), arena=True, use_pallas=True, state_codec=codec,
        m_codec=m_codec, master_params=True, work_param_cache=wp,
        finite_guard=finite_guard)
    opt_init = _arena_init(opt_cfg)

    def build():
        params = init_params(cfg, jax.random.key(0))
        return {"params": params, "opt": opt_init(params)}

    abstract = jax.eval_shape(build)
    return ckpt_mod.export_working_params(ckpt_dir, step, abstract)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--static", action="store_true",
                    help="static batch path instead of continuous batching")
    ap.add_argument("--chunk", type=int, default=8,
                    help="prefill chunk size (continuous mode)")
    ap.add_argument("--block", type=int, default=kv_arena.BLOCK_TOKENS,
                    help="paged-arena tokens per block (continuous mode)")
    ap.add_argument("--ckpt", default=None,
                    help="export working params from this checkpoint dir "
                         "via the master arena instead of random init")
    ap.add_argument("--ckpt-codec", default="fp32")
    ap.add_argument("--ckpt-m-codec", default="fp32")
    ap.add_argument("--ckpt-wp", action="store_true",
                    help="checkpoint carries a work_param_cache region")
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if not cfg.supports_decode:
        raise SystemExit(f"{cfg.name} is encoder-only; no decode path")
    if args.ckpt:
        params = params_from_ckpt(cfg, args.ckpt, codec=args.ckpt_codec,
                                  m_codec=args.ckpt_m_codec, wp=args.ckpt_wp)
        print(f"[serve] params exported from master arena at {args.ckpt}")
    else:
        params = init_params(cfg, jax.random.key(args.seed))

    shape = InputShape("serve", args.prompt_len, args.batch, "prefill")
    data = make_data(cfg, shape, seed=args.seed)
    raw = data.batch(0)
    batch = {"tokens": jnp.asarray(raw["tokens"])}
    if cfg.arch_type == "audio":
        batch["frames"] = jnp.asarray(raw["frames"])
    if cfg.arch_type == "vlm":
        batch["patches"] = jnp.asarray(raw["patches"])

    if args.static or cfg.arch_type in ("audio", "vlm"):
        # audio/vlm prompts carry encoder towers; they serve via the
        # one-shot prefill admission path, which the static loop exercises
        tokens, st = run_static(cfg, params, batch, args.prompt_len, args.gen)
        print(f"[serve] prefill {args.prompt_len} tokens x{args.batch}: "
              f"{st['prefill_s']:.2f}s")
        print(f"[serve] decoded {args.gen} tokens x{args.batch} in "
              f"{st['decode_s']:.2f}s ({st['tok_per_s']:.1f} tok/s)")
        print("[serve] sample:", tokens[0].tolist())
        return

    prompts = np.asarray(raw["tokens"], np.int32)
    srv = DecodeServer(cfg, params, max_len=args.prompt_len + args.gen,
                       width=args.batch, block=args.block, chunk=args.chunk)
    for i in range(args.batch):
        srv.submit(Request(i, prompts[i], args.gen))
    t0 = time.perf_counter()
    done = srv.run()
    dt = time.perf_counter() - t0
    n_tok = sum(len(r.out) for r in done)
    print(f"[serve] continuous: {len(done)} requests, {n_tok} tokens in "
          f"{dt:.2f}s ({n_tok / dt:.1f} tok/s, {srv.ticks} ticks, "
          f"peak paged bytes {srv.alloc.peak_bytes})")
    print("[serve] sample:", done[0].out)


if __name__ == "__main__":
    main()
