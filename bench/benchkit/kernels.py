"""Finding the arena kernels in a trace and their roofline share.

The step's Mosaic kernels are the `tpu_custom_call` instructions of the
compiled step's HLO. The fold runs once per micro-batch and the apply once
per step, so each is told apart by how many times it ran per step of the
window. A kernel that cannot be found that way gives no reading."""
from __future__ import annotations

from benchkit import flops
from benchkit.trace import custom_calls


def find(ctx):
    """{"fold": name, "apply": name} of the step's arena kernels."""
    if ctx.trace is None or ctx.info.compiled is None or not ctx.info.steps:
        return {}
    names = set(custom_calls(ctx.info.compiled.as_text()))
    chip = min(ctx.trace.ops)
    counts = ctx.trace.op_counts(chip)
    n_micro = ctx.cell.traffic["micro_batches"]
    out = {}
    for name in names:
        per_step = counts.get(name, 0) / ctx.info.steps
        if abs(per_step - n_micro) < 0.5 and n_micro > 1:
            out["fold"] = name
        elif abs(per_step - 1) < 0.5:
            out["apply"] = name
    return out


def roofline(ctx, role: str):
    """Share (%) of the HBM roofline of the `role` kernel, mean over
    chips; None when the cell runs no such kernel."""
    name = find(ctx).get(role)
    if name is None or not ctx.info.arena_rows:
        return None
    opt = ctx.run.optimizer
    rows = ctx.info.arena_rows // ctx.info.state_shards
    if role == "fold":
        per_call = flops.fold_bytes(rows, opt.m_codec, opt.state_codec,
                                    opt.grad_dtype)
    else:
        per_call = flops.apply_bytes(rows, opt.m_codec, opt.state_codec,
                                     emit_work=opt.master_params)
    shares = []
    for chip in ctx.trace.ops:
        secs = ctx.trace.op_seconds(chip).get(name, 0.0)
        calls = ctx.trace.op_counts(chip).get(name, 0)
        if secs > 0:
            shares.append(per_call * calls
                          / ctx.peaks["hbm_bytes_per_s"] / secs)
    return 100.0 * sum(shares) / len(shares) if shares else None
