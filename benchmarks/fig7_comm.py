"""Fig. 7 analog: the throughput claim, restated as communication volume
(no TPU clock in this container — see DESIGN.md §2).

Per mini-batch collective volume in the data-parallel engine:
  GA      ~ 1x params  (one grad all-reduce)
  AdamA   ~ 2x params  (one m + one v all-reduce)  — constant in N
  naive   ~ N x params (grad all-reduce per micro-batch)

Also reports the CPU wall-clock of a real (reduced-model) step for each
engine as the us_per_call column."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
import time
from pathlib import Path

from benchmarks.common import row

CODE = """
    import dataclasses, json, time
    import jax, jax.numpy as jnp
    from jax.sharding import AxisType
    from repro.configs import get_config, OptimizerConfig
    from repro.models.model import init_params, abstract_params
    from repro.core.dp_shardmap import make_dp_train_step
    from repro.launch.hlo_analysis import analyze_collectives
    cfg = dataclasses.replace(get_config('bert_large').reduced(),
                              compute_dtype='float32')
    aparams = abstract_params(cfg)
    P_bytes = sum(x.size * 4 for x in jax.tree.leaves(aparams))
    M = 4
    mesh = jax.make_mesh((M,), ('data',), axis_types=(AxisType.Auto,))
    params = init_params(cfg, jax.random.key(0))
    out = {}
    for N in (2, 4, 8):
        tokens = jax.random.randint(jax.random.key(1), (4 * N, 32), 0,
                                    cfg.vocab_size)
        batch = {'tokens': tokens, 'labels': tokens}
        for variant in ('ga', 'adama', 'naive'):
            oc = OptimizerConfig(name='adama', accumulation='adama',
                                 micro_batches=N)
            step, init = make_dp_train_step(cfg, oc, mesh, ('data',), variant)
            st = init(params)
            with mesh:
                jstep = jax.jit(step)
                comp = jstep.lower(params, st, batch).compile()
                t0 = time.perf_counter()
                p2, s2, _ = jstep(params, st, batch)
                jax.block_until_ready(p2)
                dt = time.perf_counter() - t0
            coll = analyze_collectives(comp.as_text())
            out[f'{variant}_n{N}'] = {
                'ar_raw_over_P': coll['all-reduce_raw'] / P_bytes,
                'wall_us': dt * 1e6}
    print('RESULT ' + json.dumps(out))
"""


def main():
    env = dict(os.environ)
    # a fake-device HLO analysis: the child stays on the CPU, off any chip
    # this process may hold
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    root = Path(__file__).resolve().parent.parent
    env["PYTHONPATH"] = f"{root/'src'}:{root}"
    t0 = time.perf_counter()
    p = subprocess.run([sys.executable, "-c", textwrap.dedent(CODE)],
                       capture_output=True, text=True, env=env, timeout=2400)
    us = (time.perf_counter() - t0) * 1e6
    if p.returncode != 0:
        row("fig7/comm", us, f"FAILED:{p.stderr[-200:]}")
        raise SystemExit(1)
    res = json.loads([l for l in p.stdout.splitlines()
                      if l.startswith("RESULT ")][-1][7:])
    for n in (2, 4, 8):
        ga = res[f"ga_n{n}"]
        ad = res[f"adama_n{n}"]
        nv = res[f"naive_n{n}"]
        row(f"fig7/comm_n{n}", ad["wall_us"],
            f"ga_vol={ga['ar_raw_over_P']:.2f}P;"
            f"adama_vol={ad['ar_raw_over_P']:.2f}P;"
            f"naive_vol={nv['ar_raw_over_P']:.2f}P;"
            f"ga_us={ga['wall_us']:.0f};naive_us={nv['wall_us']:.0f}")


if __name__ == "__main__":
    main()
