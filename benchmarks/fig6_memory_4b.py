"""Fig. 6 analog: BERT-4B (GPT-3-style scaling), GA vs AdamA (a), and
+ZeRO-1 sharding of the AdamA states in 8-way data parallel (b).

Paper claim (a): 23.2% memory saving at 4B params; (b) ZeRO-DP P_os + AdamA
stacks both savings."""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap
import time
from pathlib import Path

from benchmarks.common import row
from benchmarks.memlib import bert_scaled, train_step_memory
from repro.configs import OptimizerConfig

B, S, N = 64, 128, 8


def main():
    cfg = bert_scaled(4e9)
    t0 = time.perf_counter()
    mems = {}
    for accum in ("ga", "adama", "adama_layerwise"):
        opt = OptimizerConfig(name="adama" if accum != "ga" else "adam",
                              accumulation=accum, micro_batches=N)
        mems[accum] = train_step_memory(cfg, B, S, opt)["peak"]
    us = (time.perf_counter() - t0) * 1e6
    pct = 100 * (mems["ga"] - mems["adama"]) / mems["ga"]
    pct_lw = 100 * (mems["ga"] - mems["adama_layerwise"]) / mems["ga"]
    row("fig6a/bert4b", us,
        f"ga_gib={mems['ga']/2**30:.1f};adama_gib={mems['adama']/2**30:.1f};"
        f"layerwise_gib={mems['adama_layerwise']/2**30:.1f};"
        f"saved_pct={pct:.1f};saved_pct_layerwise={pct_lw:.1f}")

    # (b) ZeRO-1: m,v sharded over an 8-way data mesh (subprocess: needs its
    # own fake device count)
    code = textwrap.dedent("""
        import os
        import jax, jax.numpy as jnp, json
        from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
        from benchmarks.memlib import bert_scaled
        from repro.configs import OptimizerConfig
        from repro.configs.base import InputShape
        from repro.core.accumulation import make_train_step
        from repro.launch.specs import train_specs
        from repro.models.model import abstract_params
        from repro.sharding.rules import Rules
        cfg = bert_scaled(4e9)
        mesh = jax.make_mesh((8,), ('data',), axis_types=(AxisType.Auto,))
        out = {}
        for accum, zero1 in (('ga', False), ('adama', False), ('adama', True)):
            opt = OptimizerConfig(name='adama' if accum != 'ga' else 'adam',
                                  accumulation=accum, micro_batches=%d)
            step, opt_init = make_train_step(cfg, opt, remat=True)
            rules = Rules(cfg, mesh, fsdp=False)
            ap = abstract_params(cfg)
            ao = jax.eval_shape(opt_init, ap)
            psh = jax.tree.map(lambda s: NamedSharding(mesh, s), rules.params_pspecs(ap))
            osh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                               rules.opt_pspecs(ao, ap, zero1=zero1))
            batch = train_specs(cfg, InputShape('m', %d, %d, 'train'))
            bsh = jax.tree.map(lambda s: NamedSharding(mesh, s), rules.batch_pspecs(batch))
            with mesh:
                comp = jax.jit(step, in_shardings=(psh, osh, bsh),
                               out_shardings=(psh, osh, NamedSharding(mesh, P())),
                               donate_argnums=(0, 1)).lower(ap, ao, batch).compile()
            ma = comp.memory_analysis()
            out[f'{accum}_zero{int(zero1)}'] = (ma.argument_size_in_bytes +
                ma.output_size_in_bytes + ma.temp_size_in_bytes -
                ma.alias_size_in_bytes)
        print('RESULT ' + json.dumps(out))
    """ % (N, S, B))
    env = dict(os.environ)
    # a fake-device HLO analysis: the child stays on the CPU, off any chip
    # this process may hold
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = str(Path(__file__).resolve().parent.parent / "src") \
        + ":" + str(Path(__file__).resolve().parent.parent)
    t0 = time.perf_counter()
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=2400)
    us = (time.perf_counter() - t0) * 1e6
    if p.returncode != 0:
        row("fig6b/bert4b_zero1_dp8", us, f"FAILED:{p.stderr[-200:]}")
        return
    import json
    res = json.loads([l for l in p.stdout.splitlines()
                      if l.startswith("RESULT ")][-1][7:])
    row("fig6b/bert4b_zero1_dp8", us,
        f"ga_perdev_gib={res['ga_zero0']/2**30:.1f};"
        f"adama_perdev_gib={res['adama_zero0']/2**30:.1f};"
        f"adama_zero1_perdev_gib={res['adama_zero1']/2**30:.1f}")


if __name__ == "__main__":
    main()
