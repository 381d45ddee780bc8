"""Table 3 analog: largest BERT-family model fitting a per-device HBM budget
under GA / AdamA / ZeRO-1 / ZeRO-1+AdamA (8-way DP, like the paper's 8-GPU
DGX rows). Budget = 16 GiB (TPU v5e) and 80 GiB (DGX-A100 row).

Paper: AdamA fits 1.26-1.33x larger than GA; ZeRO-1+AdamA fits ~3.1x larger
than ZeRO-1 alone."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
import time
from pathlib import Path

from benchmarks.common import row

B, S, N = 64, 128, 8
SIZES = [1e9, 2e9, 4.5e9, 9e9, 18e9]

CODE = """
    import jax, jax.numpy as jnp, json
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
    from benchmarks.memlib import bert_scaled
    from repro.configs import OptimizerConfig
    from repro.configs.base import InputShape
    from repro.core.accumulation import make_train_step
    from repro.launch.specs import train_specs
    from repro.models.model import abstract_params, count_params_analytic
    from repro.sharding.rules import Rules
    import sys
    size, scheme = float(sys.argv[1]), sys.argv[2]
    cfg = bert_scaled(size)
    accum = 'adama' if 'adama' in scheme else 'ga'
    zero1 = 'zero1' in scheme
    opt = OptimizerConfig(name='adama' if accum != 'ga' else 'adam',
                          accumulation=accum, micro_batches=%d)
    mesh = jax.make_mesh((8,), ('data',), axis_types=(AxisType.Auto,))
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
    peak = (ma.argument_size_in_bytes + ma.output_size_in_bytes +
            ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    print('RESULT ' + json.dumps({'peak': peak,
                                  'n_params': count_params_analytic(cfg)}))
""" % (N, S, B)


def _peak(size, scheme):
    env = dict(os.environ)
    # a fake-device HLO analysis: the child stays on the CPU, off any chip
    # this process may hold
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    root = Path(__file__).resolve().parent.parent
    env["PYTHONPATH"] = f"{root/'src'}:{root}"
    p = subprocess.run([sys.executable, "-c", textwrap.dedent(CODE),
                        str(size), scheme],
                       capture_output=True, text=True, env=env, timeout=2400)
    if p.returncode != 0:
        raise RuntimeError(p.stderr[-400:])
    res = json.loads([l for l in p.stdout.splitlines()
                      if l.startswith("RESULT ")][-1][7:])
    return res["peak"], res["n_params"]


def main():
    budgets = {"v5e16": 16 * 2**30, "a100_80": 80 * 2**30}
    t_all = time.perf_counter()
    results = {}
    for scheme in ("ga", "adama", "zero1", "zero1_adama"):
        fits = {k: (0, 0) for k in budgets}
        for size in SIZES:
            try:
                peak, n = _peak(size, scheme)
            except RuntimeError as e:
                print(f"# table3 {scheme} size={size:.0e} failed: {e}",
                      flush=True)
                break
            done = True
            for k, budget in budgets.items():
                if peak <= budget:
                    fits[k] = (n, peak)
                if peak <= budget:
                    done = False
            if done:
                break
        results[scheme] = fits
    us = (time.perf_counter() - t_all) * 1e6
    for k in budgets:
        derived = ";".join(
            f"{scheme}_maxB={results[scheme][k][0]/1e9:.1f}"
            for scheme in results)
        ga_n = results["ga"][k][0] or 1
        z_n = results["zero1"][k][0] or 1
        derived += (f";adama_vs_ga={results['adama'][k][0]/ga_n:.2f}x"
                    f";zero1adama_vs_zero1={results['zero1_adama'][k][0]/z_n:.2f}x")
        row(f"table3/{k}", us / len(budgets), derived)


if __name__ == "__main__":
    main()
