"""Distributed tests — run in subprocesses so each picks its own fake device
count (jax locks the device count at first init; the main pytest process must
keep the single real CPU device)."""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

SRC = str(Path(__file__).resolve().parent.parent / "src")


def run_sub(code: str, devices: int = 8, timeout: int = 900) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, timeout=timeout,
                         env=env)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


def test_dp_adama_equals_single_device_nm():
    """Paper §3.3: AdamA on (M devices, N micro) == single device (N*M micro),
    via the M*beta2 pre-scale and /M, /M^2 all-reduce corrections."""
    out = run_sub("""
        import dataclasses, jax, jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        from repro.configs import get_config, OptimizerConfig
        from repro.models.model import init_params
        from repro.core.accumulation import make_train_step
        from repro.core.dp_shardmap import make_dp_train_step
        cfg = dataclasses.replace(get_config('stablelm_1_6b').reduced(),
                                  compute_dtype='float32')
        params = init_params(cfg, jax.random.key(0))
        tokens = jax.random.randint(jax.random.key(1), (8, 16), 0, cfg.vocab_size)
        batch = {'tokens': tokens, 'labels': tokens}
        M, N = 4, 2
        mesh = make_mesh((M,), ('data',))
        oc = OptimizerConfig(name='adama', accumulation='adama', micro_batches=N*M)
        step_s, init_s = make_train_step(cfg, oc)
        p_s, st_s, _ = jax.jit(step_s)(params, init_s(params), batch)
        oc2 = dataclasses.replace(oc, micro_batches=N)
        step_d, init_d = make_dp_train_step(cfg, oc2, mesh, ('data',), 'adama')
        with mesh:
            p_d, st_d, _ = jax.jit(step_d)(params, init_d(params), batch)
        d = max(float(jnp.max(jnp.abs(a - b)))
                for a, b in zip(jax.tree.leaves(p_s), jax.tree.leaves(p_d)))
        dv = max(float(jnp.max(jnp.abs(a - b)))
                 for a, b in zip(jax.tree.leaves(st_s['v']), jax.tree.leaves(st_d['v'])))
        print('PDIFF', d, 'VDIFF', dv)
        assert d < 1e-6 and dv < 1e-8, (d, dv)
    """, devices=4)
    assert "PDIFF" in out


def test_dp_adama_arena_equals_tree_state():
    """The flat-arena optimizer path composes with the §3.3 DP schedule:
    psum over the (m, v) arena buffers + fused decay/fold produce the same
    update as the per-leaf tree state."""
    out = run_sub("""
        import dataclasses, jax, jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        from repro.configs import get_config, OptimizerConfig
        from repro.models.model import init_params
        from repro.core.dp_shardmap import make_dp_train_step
        cfg = dataclasses.replace(get_config('stablelm_1_6b').reduced(),
                                  compute_dtype='float32')
        params = init_params(cfg, jax.random.key(0))
        tokens = jax.random.randint(jax.random.key(1), (8, 16), 0, cfg.vocab_size)
        batch = {'tokens': tokens, 'labels': tokens}
        mesh = make_mesh((4,), ('data',))
        oc = OptimizerConfig(name='adama', accumulation='adama', micro_batches=2)
        oca = dataclasses.replace(oc, use_pallas=True, arena=True)
        step_t, init_t = make_dp_train_step(cfg, oc, mesh, ('data',), 'adama')
        step_a, init_a = make_dp_train_step(cfg, oca, mesh, ('data',), 'adama')
        with mesh:
            pt, st, _ = jax.jit(step_t)(params, init_t(params), batch)
            pa, sa, _ = jax.jit(step_a)(params, init_a(params), batch)
        d = max(float(jnp.max(jnp.abs(a - b)))
                for a, b in zip(jax.tree.leaves(pt), jax.tree.leaves(pa)))
        mt = sa['m'].to_tree(jnp.float32)
        dm = max(float(jnp.max(jnp.abs(a - b)))
                 for a, b in zip(jax.tree.leaves(st['m']), jax.tree.leaves(mt)))
        print('PDIFF', d, 'MDIFF', dm)
        assert d < 1e-6 and dm < 1e-6, (d, dm)
    """, devices=4)
    assert "PDIFF" in out


def test_dp_zero1_row_range_schedule_all_codecs():
    """The ZeRO-1 row-range schedule (psum_scatter gradient fold on owned
    rows, dynamic-slice apply, param all-gather — dp_shardmap.py) matches
    single-device AdamA over the same global micro-batch grouping, for
    (m_codec, v_codec) combinations covering every codec: fp32/factored to
    fp tolerance, int8 m/v within the documented quantization drift
    (<= 2*lr per step), rowcol to fp tolerance (its replicated column sums
    are per-shard partials combined by one psum per mini-batch — same math
    as unsharded, different fp summation order)."""
    out = run_sub("""
        import dataclasses, jax, jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        from repro.configs import get_config, OptimizerConfig
        from repro.models.model import init_params
        from repro.core.accumulation import make_train_step
        from repro.core.dp_shardmap import make_dp_train_step
        cfg = dataclasses.replace(get_config('stablelm_1_6b').reduced(),
                                  compute_dtype='float32')
        params = init_params(cfg, jax.random.key(0))
        tokens = jax.random.randint(jax.random.key(1), (8, 16), 0, cfg.vocab_size)
        batch = {'tokens': tokens, 'labels': tokens}
        M, N = 4, 2
        mesh = make_mesh((M,), ('data',))
        # the DP schedule folds global micro-group i = {device k's i-th local
        # micro}; reorder the reference batch so single-device fold i sees
        # exactly those rows
        B = tokens.shape[0]; b = B // (M * N)
        idx = jnp.array([k*(B//M) + i*b + j
                         for i in range(N) for k in range(M) for j in range(b)])
        ref_batch = {kk: v[idx] for kk, v in batch.items()}
        combos = (('fp32', 'fp32', 1e-5), ('fp32', 'int8', 2e-3),
                  ('fp32', 'factored', 1e-5), ('fp32', 'rowcol', 1e-4),
                  ('int8', 'fp32', 2e-3), ('int8', 'int8', 4e-3),
                  ('int8', 'rowcol', 2e-3))
        for m_codec, v_codec, tol in combos:
            # reference: one device folds the SAME N global micro-batches
            oc = OptimizerConfig(name='adama', accumulation='adama',
                                 micro_batches=N, use_pallas=True, arena=True,
                                 state_codec=v_codec, m_codec=m_codec)
            step_s, init_s = make_train_step(cfg, oc)
            p_s, st_s, _ = jax.jit(step_s)(params, init_s(params), ref_batch)
            ocz = dataclasses.replace(oc, zero_stage=1)
            step_z, init_z = make_dp_train_step(cfg, ocz, mesh, ('data',),
                                                'adama')
            with mesh:
                p_z, st_z, _ = jax.jit(step_z)(params, init_z(params), batch)
            d = max(float(jnp.max(jnp.abs(a - b)))
                    for a, b in zip(jax.tree.leaves(p_s), jax.tree.leaves(p_z)))
            print('CODEC', m_codec + ':' + v_codec, 'PDIFF', d)
            assert d < tol, (m_codec, v_codec, d, tol)
            assert int(st_z['step']) == 1
    """, devices=4, timeout=1800)
    for combo in ("fp32:fp32", "fp32:int8", "fp32:factored", "fp32:rowcol",
                  "int8:fp32", "int8:int8", "int8:rowcol"):
        assert f"CODEC {combo}" in out


def test_dp_zero1_bucketed_bitwise_matches_full_pack():
    """Tentpole acceptance: the bucketed ZeRO-1 schedule (per-bucket
    psum_scatter streamed into slice folds, state resident in partition
    order — core/buckets.py) is BITWISE identical to the legacy full-pack
    schedule on 4 fake devices: params bitwise for every tested codec pair,
    sharded state bitwise after unpermuting row-indexed columns back to
    arena order (rowcol's replicated column sums accumulate per-device
    partials over different row groupings, so they — and everything
    downstream of them — compare to fp summation-order tolerance instead).
    Also the memory claim, from the compiled HLO: the bucketed step's
    largest reduce-scatter operand is <= the plan's max-bucket budget,
    while full-pack's equals the whole gradient arena."""
    out = run_sub("""
        import dataclasses, jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.configs import get_config, OptimizerConfig
        from repro.models.model import init_params
        from repro.core.dp_shardmap import make_dp_train_step
        from repro.core import buckets as buckets_mod
        from repro.core.zero import zero1_bucket_plan
        from repro.launch.hlo_analysis import analyze_hlo
        from repro.kernels.adama_accum import LANES
        cfg = dataclasses.replace(get_config('stablelm_1_6b').reduced(),
                                  compute_dtype='float32')
        params = init_params(cfg, jax.random.key(0))
        tokens = jax.random.randint(jax.random.key(1), (8, 16), 0, cfg.vocab_size)
        batch = {'tokens': tokens, 'labels': tokens}
        M, N = 4, 2
        mesh = make_mesh((M,), ('data',))
        combos = (('fp32', 'fp32'), ('fp32', 'int8'), ('int8', 'int8'),
                  ('fp32', 'factored'), ('int8', 'rowcol'))
        checked_hlo = False
        for m_codec, v_codec in combos:
            ocb = OptimizerConfig(name='adama', accumulation='adama',
                                  micro_batches=N, use_pallas=True, arena=True,
                                  zero_stage=1, state_codec=v_codec,
                                  m_codec=m_codec)
            ocf = dataclasses.replace(ocb, zero_bucketed=False)
            step_b, init_b = make_dp_train_step(cfg, ocb, mesh, ('data',), 'adama')
            step_f, init_f = make_dp_train_step(cfg, ocf, mesh, ('data',), 'adama')
            with mesh:
                pb, sb, mb = jax.jit(step_b)(params, init_b(params), batch)
                pf, sf, mf = jax.jit(step_f)(params, init_f(params), batch)
            rowcol = v_codec == 'rowcol'
            pd = max(float(jnp.max(jnp.abs(a - b)))
                     for a, b in zip(jax.tree.leaves(pb), jax.tree.leaves(pf)))
            print('COMBO', m_codec + ':' + v_codec, 'PDIFF', pd)
            assert (pd < 1e-6 if rowcol else pd == 0.0), (m_codec, v_codec, pd)
            assert float(mb['loss']) == float(mf['loss'])
            # sharded state: unpermute partition order -> arena order
            lay = sb['m'].layout
            plan = zero1_bucket_plan(lay, M)
            su = buckets_mod.unpermute_state(sb, plan)
            for k in ('m', 'v'):
                for a, b in zip(jax.tree.leaves(su[k]), jax.tree.leaves(sf[k])):
                    a, b = np.asarray(a), np.asarray(b)
                    if rowcol:
                        np.testing.assert_allclose(
                            a.astype(np.float64), b.astype(np.float64),
                            rtol=1e-5, atol=1e-7)
                    else:
                        assert np.array_equal(a, b), (m_codec, v_codec, k)
            if not checked_hlo:     # memory claim, once (HLO is codec-invariant)
                with mesh:
                    hb = analyze_hlo(jax.jit(step_b).lower(
                        params, init_b(params), batch).compile().as_text())
                    hf = analyze_hlo(jax.jit(step_f).lower(
                        params, init_f(params), batch).compile().as_text())
                peak_b = hb['maxop_reduce-scatter']
                peak_f = hf['maxop_reduce-scatter']
                budget = plan.max_grad_bucket_bytes
                arena_bytes = lay.rows * LANES * 4
                print('GRAD_PEAK bucketed', peak_b, 'budget', budget,
                      'fullpack', peak_f, 'arena', arena_bytes)
                assert peak_b <= budget < arena_bytes, (peak_b, budget)
                assert peak_f == arena_bytes, (peak_f, arena_bytes)
                checked_hlo = True
    """, devices=4, timeout=1800)
    for combo in ("fp32:fp32", "fp32:int8", "int8:int8", "fp32:factored",
                  "int8:rowcol"):
        assert f"COMBO {combo}" in out
    assert "GRAD_PEAK" in out


def test_dp_zero1_layerwise_stream_matches_single_device():
    """The layer-wise engine's ZeRO-1 gap, closed: variant='adama_layerwise'
    streams each layer's gradient slab through its own psum_scatter out of
    the backward scan (no gradient tree, no gradient arena) and matches
    single-device AdamA over the same global micro-batch grouping within
    the engine tolerances (the layerwise VJP pre-scales gradients through
    the cotangent, so cross-engine parity is tolerance, not bitwise)."""
    out = run_sub("""
        import dataclasses, jax, jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        from repro.configs import get_config, OptimizerConfig
        from repro.models.model import init_params
        from repro.core.accumulation import make_train_step
        from repro.core.dp_shardmap import make_dp_train_step
        cfg = dataclasses.replace(get_config('stablelm_1_6b').reduced(),
                                  compute_dtype='float32')
        params = init_params(cfg, jax.random.key(0))
        tokens = jax.random.randint(jax.random.key(1), (8, 16), 0, cfg.vocab_size)
        batch = {'tokens': tokens, 'labels': tokens}
        M, N = 4, 2
        mesh = make_mesh((M,), ('data',))
        B = tokens.shape[0]; b = B // (M * N)
        idx = jnp.array([k*(B//M) + i*b + j
                         for i in range(N) for k in range(M) for j in range(b)])
        ref_batch = {kk: v[idx] for kk, v in batch.items()}
        for m_codec, v_codec, tol in (('fp32', 'fp32', 2e-5),
                                      ('int8', 'int8', 4e-3),
                                      ('fp32', 'rowcol', 1e-4)):
            oc = OptimizerConfig(name='adama', accumulation='adama',
                                 micro_batches=N, use_pallas=True, arena=True,
                                 state_codec=v_codec, m_codec=m_codec)
            step_s, init_s = make_train_step(cfg, oc)
            p_s, _, _ = jax.jit(step_s)(params, init_s(params), ref_batch)
            ocz = dataclasses.replace(oc, zero_stage=1)
            step_z, init_z = make_dp_train_step(cfg, ocz, mesh, ('data',),
                                                'adama_layerwise')
            with mesh:
                p_z, st_z, _ = jax.jit(step_z)(params, init_z(params), batch)
            d = max(float(jnp.max(jnp.abs(a - b)))
                    for a, b in zip(jax.tree.leaves(p_s), jax.tree.leaves(p_z)))
            print('LW', m_codec + ':' + v_codec, 'PDIFF', d)
            assert d < tol, (m_codec, v_codec, d, tol)
            assert int(st_z['step']) == 1
        # guard: the layerwise shard_map variant exists only as ZeRO-1 stream
        try:
            make_dp_train_step(cfg, oc, mesh, ('data',), 'adama_layerwise')
            raise SystemExit('expected ValueError')
        except ValueError as e:
            assert 'zero_stage=1' in str(e)
        print('GUARD OK')
    """, devices=4, timeout=1800)
    for combo in ("fp32:fp32", "int8:int8", "fp32:rowcol"):
        assert f"LW {combo}" in out
    assert "GUARD OK" in out


def test_dp_zero1_bf16_wire_and_master_params():
    """Mixed-precision AdamA under the bucketed ZeRO-1 schedule (PR 5
    tentpole): grad_dtype=bf16 + master_params on 4 fake devices

      * matches the single-device mixed-precision run over the same global
        micro-batch grouping within the bf16-wire tolerance (the DP wire
        rounds each device's contribution to bf16 BEFORE the psum, the
        single-device wire rounds the combined gradient once — same
        contract the capability matrix documents as to-tolerance);
      * the fp32 master region row-shards, stays fp32, and the returned
        working params are exactly its bf16 round (AMP round-trip by
        construction);
      * the WIRE memory/comm claim, from the pre-optimization HLO (the
        program's collective dtypes; XLA CPU re-widens them post-opt):
        largest gradient reduce-scatter operand and total collective bytes
        both <= 0.55x the fp32-wire bucketed schedule."""
    out = run_sub("""
        import dataclasses, jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.configs import get_config, OptimizerConfig
        from repro.models.model import init_params
        from repro.core.accumulation import make_train_step
        from repro.core.dp_shardmap import make_dp_train_step
        from repro.core import arena as arena_mod
        from repro.launch.hlo_analysis import analyze_hlo
        cfg = dataclasses.replace(get_config('stablelm_1_6b').reduced(),
                                  compute_dtype='float32')
        params = init_params(cfg, jax.random.key(0))
        tokens = jax.random.randint(jax.random.key(1), (8, 16), 0, cfg.vocab_size)
        batch = {'tokens': tokens, 'labels': tokens}
        M, N = 4, 2
        mesh = make_mesh((M,), ('data',))
        B = tokens.shape[0]; b = B // (M * N)
        idx = jnp.array([k*(B//M) + i*b + j
                         for i in range(N) for k in range(M) for j in range(b)])
        ref_batch = {kk: v[idx] for kk, v in batch.items()}
        base = dict(name='adama', accumulation='adama', micro_batches=N,
                    use_pallas=True, arena=True, zero_stage=1)
        oc_f = OptimizerConfig(**base)
        oc_b = OptimizerConfig(**base, grad_dtype='bf16', master_params=True)
        step_f, init_f = make_dp_train_step(cfg, oc_f, mesh, ('data',), 'adama')
        step_b, init_b = make_dp_train_step(cfg, oc_b, mesh, ('data',), 'adama')
        with mesh:
            pb, sb, mb = jax.jit(step_b)(params, init_b(params), batch)
            lf = jax.jit(step_f).lower(params, init_f(params), batch)
            lb = jax.jit(step_b).lower(params, init_b(params), batch)
        # single-device mixed-precision reference, same global grouping
        oc_s = OptimizerConfig(name='adama', accumulation='adama',
                               micro_batches=N, use_pallas=True, arena=True,
                               grad_dtype='bf16', master_params=True)
        step_s, init_s = make_train_step(cfg, oc_s)
        ps, ss, ms = jax.jit(step_s)(params, init_s(params), ref_batch)
        d = max(float(jnp.max(jnp.abs(a.astype(jnp.float32) -
                                      b_.astype(jnp.float32))))
                for a, b_ in zip(jax.tree.leaves(pb), jax.tree.leaves(ps)))
        print('MP PDIFF', d)
        assert d < 2e-3, d                      # bf16-wire + bf16 work params
        # master stays fp32 and the work params are its exact bf16 round
        assert sb['p'].data.dtype == jnp.float32
        from repro.core import buckets as buckets_mod
        from repro.core.zero import zero1_bucket_plan
        plan = zero1_bucket_plan(sb['m'].layout, M)
        master_tree = arena_mod.unpack(
            buckets_mod.unpermute_rows(sb['p'].data, plan), sb['p'].layout)
        cast = jax.tree.map(lambda x: x.astype(jnp.bfloat16).astype(x.dtype),
                            master_tree)
        dr = max(float(jnp.max(jnp.abs(a - b_)))
                 for a, b_ in zip(jax.tree.leaves(pb), jax.tree.leaves(cast)))
        print('ROUNDTRIP', dr)
        assert dr == 0.0
        # wire memory/comm: <= 0.55x the fp32 wire
        hf = analyze_hlo(lf.as_text(dialect='hlo'))
        hb = analyze_hlo(lb.as_text(dialect='hlo'))
        rs = hb['maxop_reduce-scatter'] / hf['maxop_reduce-scatter']
        co = hb['coll_total'] / hf['coll_total']
        print('WIRE ratios rs', rs, 'coll', co)
        assert rs <= 0.55 and co <= 0.55, (rs, co)
    """, devices=4, timeout=1800)
    assert "MP PDIFF" in out
    assert "ROUNDTRIP 0.0" in out
    assert "WIRE ratios" in out


def test_dp_zero1_fp8_wire_error_feedback():
    """fp8_e4m3 gradient wire under the bucketed ZeRO-1 schedule (PR 8
    tentpole) on 4 fake devices: per-bucket e4m3 codes + pmax-agreed scale
    columns through every gradient reduce-scatter, the param all-gather
    quantized the same way, accuracy recovered by the row-sharded
    error-feedback residual.

      * the fp8+EF trajectory tracks the fp32-wire bucketed run within the
        documented (2+2)*lr*2 headroom over 2 steps, for BOTH shard_map
        variants (adama and the layerwise stream), and the residual region
        comes back finite and non-trivial;
      * the WIRE claim from the pre-optimization HLO: largest gradient
        reduce-scatter operand and total collective bytes both <= 0.3x the
        fp32-wire bucketed schedule (1-byte codes + fp32 scale columns +
        agreement pmax stay under the gate step_bench enforces);
      * the capability refusals name the fix: fp8 over shard_map DP
        without the bucketed schedule, or without master params, and
        work_param_cache on any shard_map engine."""
    out = run_sub("""
        import dataclasses, jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.configs import get_config, OptimizerConfig
        from repro.models.model import init_params
        from repro.core.dp_shardmap import make_dp_train_step
        from repro.launch.hlo_analysis import analyze_hlo
        cfg = dataclasses.replace(get_config('stablelm_1_6b').reduced(),
                                  compute_dtype='float32')
        params = init_params(cfg, jax.random.key(0))
        tokens = jax.random.randint(jax.random.key(1), (8, 16), 0, cfg.vocab_size)
        batch = {'tokens': tokens, 'labels': tokens}
        M = 4
        mesh = make_mesh((M,), ('data',))
        def opt(**kw):
            base = dict(name='adama', accumulation='adama', micro_batches=2,
                        use_pallas=True, arena=True, zero_stage=1,
                        zero_bucketed=True, master_params=True,
                        finite_guard=True)
            base.update(kw)
            return OptimizerConfig(**base)
        def run(oc, variant='adama', steps=2):
            step, init = make_dp_train_step(cfg, oc, mesh, ('data',), variant)
            with mesh:
                p, st = params, init(params)
                f = jax.jit(step)
                for _ in range(steps):
                    p, st, mx = f(p, st, batch)
            return p, st, f
        oc_f = opt()
        oc_8 = opt(grad_dtype='fp8_e4m3', loss_scale='256')
        p32, st32, f32 = run(oc_f)
        p8, st8, f8 = run(oc_8)
        ef = np.asarray(st8['ef'].data)
        assert np.isfinite(ef).all() and np.abs(ef).max() > 0
        d = max(float(jnp.max(jnp.abs(a - b)))
                for a, b in zip(jax.tree.leaves(p32), jax.tree.leaves(p8)))
        print('FP8 PDIFF', d)
        assert d < 8e-3, d
        pl, stl, _ = run(oc_8, variant='adama_layerwise')
        dl = max(float(jnp.max(jnp.abs(a - b)))
                 for a, b in zip(jax.tree.leaves(p32), jax.tree.leaves(pl)))
        print('FP8 LAYERWISE PDIFF', dl)
        assert dl < 8e-3, dl
        assert bool((np.asarray(stl['ef'].data) != 0).any())
        # wire memory/comm vs the PLAIN fp32 bucketed schedule (the same
        # reference row step_bench gates against — no master params, whose
        # bf16 working-row gather would shrink the denominator): <= 0.3x
        oc_p = opt(master_params=False, finite_guard=False)
        with mesh:
            stp_f, ini_f = make_dp_train_step(cfg, oc_p, mesh, ('data',), 'adama')
            stp_8, ini_8 = make_dp_train_step(cfg, oc_8, mesh, ('data',), 'adama')
            lf = jax.jit(stp_f).lower(params, ini_f(params), batch)
            l8 = jax.jit(stp_8).lower(params, ini_8(params), batch)
        hf = analyze_hlo(lf.as_text(dialect='hlo'))
        h8 = analyze_hlo(l8.as_text(dialect='hlo'))
        rs = h8['maxop_reduce-scatter'] / hf['maxop_reduce-scatter']
        co = h8['coll_total'] / hf['coll_total']
        print('FP8 WIRE ratios rs', rs, 'coll', co)
        assert rs <= 0.3 and co <= 0.3, (rs, co)
        # refusals name the fix
        for kw, pat in [(dict(grad_dtype='fp8_e4m3', loss_scale='256',
                              zero_bucketed=False), 'bucketed'),
                        (dict(grad_dtype='fp8_e4m3', loss_scale='256',
                              master_params=False), 'master_params'),
                        (dict(work_param_cache=True), 'work_param_cache')]:
            try:
                make_dp_train_step(cfg, opt(**kw), mesh, ('data',), 'adama')
            except ValueError as e:
                assert pat in str(e), (pat, str(e))
            else:
                raise SystemExit('missing refusal: ' + pat)
        print('REFUSALS OK')
    """, devices=4, timeout=1800)
    assert "FP8 PDIFF" in out
    assert "FP8 WIRE ratios" in out
    assert "REFUSALS OK" in out


def test_bucketed_checkpoint_roundtrip_into_full_pack():
    """PR-4 ROADMAP follow-on, closed: checkpointing a bucketed shard_map
    run auto-unpermutes to canonical arena order (ckpt.save(bucket_plan=))
    and re-permutes on resume (ckpt.restore(bucket_plan=)). Proven by the
    full round trip on 4 fake devices: a bucketed step-1 checkpoint is
    BITWISE the full-pack step-1 checkpoint; resuming it into a FULL-PACK
    run reproduces the continuous full-pack step 2 bitwise; resuming it
    back into a bucketed run reproduces the same step-2 params bitwise."""
    out = run_sub("""
        import dataclasses, tempfile, jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.configs import get_config, OptimizerConfig
        from repro.models.model import init_params
        from repro.core.dp_shardmap import make_dp_train_step
        from repro.core.zero import zero1_bucket_plan
        from repro.train import checkpoint as ckpt
        cfg = dataclasses.replace(get_config('stablelm_1_6b').reduced(),
                                  compute_dtype='float32')
        params = init_params(cfg, jax.random.key(0))
        t1 = jax.random.randint(jax.random.key(1), (8, 16), 0, cfg.vocab_size)
        t2 = jax.random.randint(jax.random.key(2), (8, 16), 0, cfg.vocab_size)
        b1 = {'tokens': t1, 'labels': t1}
        b2 = {'tokens': t2, 'labels': t2}
        M = 4
        mesh = make_mesh((M,), ('data',))
        ocb = OptimizerConfig(name='adama', accumulation='adama',
                              micro_batches=2, use_pallas=True, arena=True,
                              zero_stage=1)
        ocf = dataclasses.replace(ocb, zero_bucketed=False)
        step_b, init_b = make_dp_train_step(cfg, ocb, mesh, ('data',), 'adama')
        step_f, init_f = make_dp_train_step(cfg, ocf, mesh, ('data',), 'adama')
        with mesh:
            # continuous runs
            pb1, sb1, _ = jax.jit(step_b)(params, init_b(params), b1)
            pf1, sf1, _ = jax.jit(step_f)(params, init_f(params), b1)
            pf2, sf2, _ = jax.jit(step_f)(pf1, sf1, b2)
            pb2, sb2, _ = jax.jit(step_b)(pb1, sb1, b2)
        plan = zero1_bucket_plan(sb1['m'].layout, M)
        with tempfile.TemporaryDirectory() as d:
            # bucketed save auto-unpermutes -> canonical == full-pack save
            ckpt.save(d + '/b', 1, {'params': pb1, 'opt': sb1},
                      bucket_plan=plan)
            ckpt.save(d + '/f', 1, {'params': pf1, 'opt': sf1})
            ab = jax.eval_shape(lambda: {'params': pf1, 'opt': sf1})
            rb = ckpt.restore(d + '/b', 1, ab)
            rf = ckpt.restore(d + '/f', 1, ab)
            for a, b_ in zip(jax.tree.leaves(rb), jax.tree.leaves(rf)):
                assert np.array_equal(np.asarray(a), np.asarray(b_))
            print('CANONICAL OK')
            # resume the BUCKETED checkpoint into a FULL-PACK run
            with mesh:
                pf2r, _, _ = jax.jit(step_f)(rb['params'], rb['opt'], b2)
            for a, b_ in zip(jax.tree.leaves(pf2r), jax.tree.leaves(pf2)):
                assert np.array_equal(np.asarray(a), np.asarray(b_))
            print('RESUME FULLPACK OK')
            # resume it back into a BUCKETED run (re-permute on restore)
            rbb = ckpt.restore(d + '/b', 1, ab, bucket_plan=plan)
            with mesh:
                pb2r, _, _ = jax.jit(step_b)(rbb['params'], rbb['opt'], b2)
            for a, b_ in zip(jax.tree.leaves(pb2r), jax.tree.leaves(pb2)):
                assert np.array_equal(np.asarray(a), np.asarray(b_))
            print('RESUME BUCKETED OK')
    """, devices=4, timeout=1800)
    assert "CANONICAL OK" in out
    assert "RESUME FULLPACK OK" in out
    assert "RESUME BUCKETED OK" in out


def test_dp_comm_schedule_volumes():
    """Fig. 7's argument as HLO fact: per mini-batch collective volume is
    ~P for GA, ~2P for AdamA (m and v), ~N*P for the naive schedule."""
    out = run_sub("""
        import dataclasses, json, jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.mesh import make_mesh
        from repro.configs import get_config, OptimizerConfig
        from repro.models.model import init_params, abstract_params
        from repro.core.dp_shardmap import make_dp_train_step
        from repro.launch.hlo_analysis import analyze_collectives
        cfg = dataclasses.replace(get_config('stablelm_1_6b').reduced(),
                                  compute_dtype='float32')
        aparams = abstract_params(cfg)
        P_bytes = sum(x.size * 4 for x in jax.tree.leaves(aparams))
        M, N = 4, 4
        mesh = make_mesh((M,), ('data',))
        batch = {'tokens': jax.ShapeDtypeStruct((16, 32), jnp.int32),
                 'labels': jax.ShapeDtypeStruct((16, 32), jnp.int32)}
        vols = {}
        for variant in ('ga', 'adama', 'naive'):
            oc = OptimizerConfig(name='adama', accumulation='adama', micro_batches=N)
            step, init = make_dp_train_step(cfg, oc, mesh, ('data',), variant)
            aopt = jax.eval_shape(init, aparams)
            with mesh:
                comp = jax.jit(step).lower(aparams, aopt, batch).compile()
            coll = analyze_collectives(comp.as_text())
            vols[variant] = coll['all-reduce_raw']
        print(json.dumps({k: v / P_bytes for k, v in vols.items()}))
        r = {k: v / P_bytes for k, v in vols.items()}
        assert 0.9 < r['ga'] < 1.6, r
        assert 1.8 < r['adama'] < 2.8, r
        assert r['naive'] > N * 0.9, r
        assert abs(r['adama'] - 2.0) < abs(r['naive'] - 2.0), r
    """, devices=4)


def test_dryrun_lowers_on_small_mesh():
    """build_lowered compiles a FULL config on a small host mesh (the 16x16
    production mesh is exercised by launch/dryrun.py in its own process)."""
    run_sub("""
        import jax
        from repro.launch.mesh import make_mesh
        from repro.launch.dryrun import build_lowered
        mesh = make_mesh((2, 4), ('data', 'model'))
        for shape in ('train_4k', 'decode_32k'):
            lowered, why = build_lowered('stablelm_1_6b', shape, mesh,
                                         micro_batches=4)
            assert lowered is not None, why
            comp = lowered.compile()
            assert comp.memory_analysis().temp_size_in_bytes > 0
        print('OK')
    """, devices=8)


def test_pjit_zero1_loop_runs_arena_kernels_per_row_shard():
    """The ZeRO-1 training loop (--zero-stage 1 --arena) on a 4-device data
    mesh runs each arena kernel per row shard inside a shard_map (a TPU
    cannot partition a Mosaic kernel): m/v stay 1/4 per device, and the
    losses match zero_stage=0 on one device — for fp32 state and for
    rowcol, whose replicated column sums are folded per shard and psum'd."""
    out = run_sub("""
        import dataclasses, jax, numpy as np
        from repro.configs import get_config, OptimizerConfig, RunConfig
        from repro.configs.base import InputShape
        from repro.train.loop import train
        cfg = dataclasses.replace(get_config('bert_large').reduced(),
                                  compute_dtype='float32')
        for codec in ('fp32', 'rowcol'):
            def run(zero_stage):
                opt = OptimizerConfig(name='adama', accumulation='adama',
                                      micro_batches=2, use_pallas=True,
                                      arena=True, state_codec=codec,
                                      zero_stage=zero_stage)
                return train(RunConfig(model=cfg, optimizer=opt,
                                       shape=InputShape('t', 32, 8, 'train'),
                                       steps=3, log_every=100),
                             log_fn=lambda *_: None)
            z0, z1 = run(0), run(1)
            np.testing.assert_allclose(z1['losses'], z0['losses'], rtol=2e-5)
            m = z1['opt_state']['m'].data
            assert [s.data.shape[0] for s in m.addressable_shards] == \\
                [m.shape[0] // 4] * 4
            print(codec, 'LOSSES', z0['losses'], z1['losses'])
        print('OK')
    """, devices=4)
    assert out.strip().endswith("OK")


def test_shardmap_engine_lowers():
    run_sub("""
        import jax
        from repro.launch.mesh import make_mesh
        from repro.launch.dryrun import build_lowered
        mesh = make_mesh((2, 2, 2), ('pod', 'data', 'model'))
        lowered, why = build_lowered('stablelm_1_6b', 'train_4k', mesh,
                                     engine='shardmap', micro_batches=4,
                                     fsdp=False)
        assert lowered is not None, why
        lowered.compile()
        print('OK')
    """, devices=8)


def test_zero1_guard_one_bad_device_agreement():
    """Resilience under shard_map: a NaN born on exactly ONE device of a
    4-way DP mesh must make ALL shards skip that micro-batch (the verdict
    is psum-agreed), leaving params and both sharded moments BITWISE equal
    to a run whose guard was forced False on every device — for all four
    engine layouts: bucketed ZeRO-1, full-pack ZeRO-1, replicated, and the
    layerwise ZeroStream. Also pins guarded == legacy bitwise with no
    fault."""
    out = run_sub("""
        import dataclasses, jax, jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        from repro.configs import get_config, OptimizerConfig
        from repro.models.model import init_params
        from repro.core.dp_shardmap import make_dp_train_step
        from repro.train.faults import parse_fault
        cfg = dataclasses.replace(get_config('stablelm_1_6b').reduced(),
                                  compute_dtype='float32')
        params = init_params(cfg, jax.random.key(0))
        tokens = jax.random.randint(jax.random.key(1), (8, 16), 0, cfg.vocab_size)
        batch = {'tokens': tokens, 'labels': tokens}
        mesh = make_mesh((4,), ('data',))

        def run(oc, variant, fault=None, steps=2):
            step, init = make_dp_train_step(cfg, oc, mesh, ('data',), variant,
                                            fault=parse_fault(fault))
            p, st = params, init(params)
            with mesh:
                f = jax.jit(step)
                for _ in range(steps):
                    p, st, mx = f(p, st, batch)
            return p, st, {k: float(v) for k, v in mx.items()}

        def leaves_eq(a, b):
            la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
            assert len(la) == len(lb)
            return all(jnp.array_equal(x, y) for x, y in zip(la, lb))

        base = dict(name='adama', accumulation='adama', micro_batches=2,
                    use_pallas=True, arena=True)
        for label, oc, variant in [
            ('zero1-bucketed', OptimizerConfig(**base, zero_stage=1), 'adama'),
            ('zero1-fullpack', OptimizerConfig(**base, zero_stage=1,
                                               zero_bucketed=False), 'adama'),
            ('replicated', OptimizerConfig(**base), 'adama'),
            ('layerwise', OptimizerConfig(**dict(base,
                              accumulation='adama_layerwise'), zero_stage=1),
             'adama_layerwise'),
        ]:
            ocg = dataclasses.replace(oc, finite_guard=True)
            p0, st0, _ = run(oc, variant)
            p1, st1, _ = run(ocg, variant)
            assert leaves_eq(p0, p1), (label, 'guarded != legacy')
            pn, stn, mn = run(ocg, variant, fault='nan@micro=1,device=2,step=0')
            ps, sts, ms = run(ocg, variant, fault='skip@micro=1,step=0')
            assert leaves_eq(pn, ps), (label, 'nan != skip params')
            assert leaves_eq(stn['m'], sts['m']), (label, 'nan != skip m')
            assert leaves_eq(stn['v'], sts['v']), (label, 'nan != skip v')
            assert int(stn['step']) == 2 == int(sts['step'])
            assert mn['skipped_micro_batches'] == 1.0, (label, mn)
            assert not leaves_eq(pn, p1), (label, 'fault had no effect')
            print('OK', label)
        print('ALL-OK')
    """, devices=4)
    assert "ALL-OK" in out


def test_zero1_dynamic_scale_bf16_recovers():
    """Dynamic loss scaling over the bucketed ZeRO-1 bf16 wire: an injected
    NaN backs the scale off exactly once (2^15 -> 2^14 on every shard —
    the scaler state is replicated and updated from the agreed verdict),
    the step counter still reaches 3, and the params stay finite."""
    out = run_sub("""
        import dataclasses, jax, jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        from repro.configs import get_config, OptimizerConfig
        from repro.models.model import init_params
        from repro.core.dp_shardmap import make_dp_train_step
        from repro.train.faults import parse_fault
        cfg = dataclasses.replace(get_config('stablelm_1_6b').reduced(),
                                  compute_dtype='float32')
        params = init_params(cfg, jax.random.key(0))
        tokens = jax.random.randint(jax.random.key(1), (8, 16), 0, cfg.vocab_size)
        batch = {'tokens': tokens, 'labels': tokens}
        mesh = make_mesh((4,), ('data',))
        oc = dataclasses.replace(
            OptimizerConfig(name='adama', accumulation='adama',
                            micro_batches=2, use_pallas=True, arena=True,
                            zero_stage=1, grad_dtype='bf16',
                            finite_guard=True),
            loss_scale='dynamic')
        step, init = make_dp_train_step(cfg, oc, mesh, ('data',), 'adama',
                                        fault=parse_fault('nan@micro=1,step=0'))
        p, st = params, init(params)
        with mesh:
            f = jax.jit(step)
            for _ in range(3):
                p, st, mx = f(p, st, batch)
        mx = {k: float(v) for k, v in mx.items()}
        assert mx['loss_scale'] == 2.0 ** 14, mx
        assert int(st['step']) == 3
        assert all(jnp.isfinite(x).all() for x in jax.tree.leaves(p))
        print('OK', mx)
    """, devices=4)
    assert "OK" in out


def test_dryrun_dp_profile_shardmap_compiles():
    """Regression pin for the recorded `--engine shardmap --profile dp`
    pod16x16 failure, which had TWO layers: (1) shard_map splits
    micro-batches on the PER-DEVICE batch, so global_batch/dp_size=1 made
    micro_batches=8 impossible ('global batch 1 not divisible by micro 8')
    — build_lowered now clamps; (2) with that fixed, the pure-DP profile
    makes EVERY mesh axis manual, and shard_attention_operand's activation
    constraint naming 'model' raised "Axis: model ... is also found in
    manual_axes" — sharding ctx now drops manual axes from constraints."""
    run_sub("""
        from repro.launch.mesh import make_production_mesh
        from repro.launch.dryrun import build_lowered
        mesh = make_production_mesh()
        info = {}
        lowered, why = build_lowered('stablelm_1_6b', 'train_4k', mesh,
                                     engine='shardmap', profile='dp',
                                     micro_batches=8, info=info)
        assert lowered is not None, why
        lowered.compile()
        assert info['finite_guard'] is False
        assert info['checkpoint_retention'] == 3
        print('OK')
    """, devices=512)


def test_dp_zero1_async_pipeline_bitwise_matches_serial():
    """Tentpole acceptance: the async double-buffered bucket schedule
    (bucket i+1's pack + reduce-scatter issued before bucket i's fold, a
    two-slot window pinned by optimization_barrier — core/dp_shardmap.py)
    is BITWISE identical to the serial bucketed schedule: it reorders WHEN
    each bucket's collective is issued, never what flows through it (the
    psum_scatter itself is unchanged). Also the two-bucket residency claim
    from the compiled HLO: scheduled-liveness peak of reduce-scatter
    operands stays within TWO max-size grad buckets, and the schedule
    leaves overlap capacity (overlap_fraction > 0)."""
    out = run_sub("""
        import dataclasses, jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.configs import get_config, OptimizerConfig
        from repro.models.model import init_params
        from repro.core.dp_shardmap import make_dp_train_step
        from repro.core.zero import zero1_bucket_plan
        from repro.launch.hlo_analysis import analyze_hlo
        cfg = dataclasses.replace(get_config('stablelm_1_6b').reduced(),
                                  compute_dtype='float32')
        params = init_params(cfg, jax.random.key(0))
        tokens = jax.random.randint(jax.random.key(1), (8, 16), 0, cfg.vocab_size)
        batch = {'tokens': tokens, 'labels': tokens}
        mesh = make_mesh((4,), ('data',))
        ocs = OptimizerConfig(name='adama', accumulation='adama',
                              micro_batches=2, use_pallas=True, arena=True,
                              zero_stage=1)
        oca = dataclasses.replace(ocs, zero_async=True)
        step_s, init_s = make_dp_train_step(cfg, ocs, mesh, ('data',), 'adama')
        step_a, init_a = make_dp_train_step(cfg, oca, mesh, ('data',), 'adama')
        with mesh:
            ps, ss, ms = jax.jit(step_s)(params, init_s(params), batch)
            pa, sa, ma = jax.jit(step_a)(params, init_a(params), batch)
        pd = max(float(jnp.max(jnp.abs(a - b)))
                 for a, b in zip(jax.tree.leaves(ps), jax.tree.leaves(pa)))
        print('PDIFF', pd)
        assert pd == 0.0, pd
        assert float(ms['loss']) == float(ma['loss'])
        for k in ('m', 'v'):
            for a, b in zip(jax.tree.leaves(ss[k]), jax.tree.leaves(sa[k])):
                assert np.array_equal(np.asarray(a), np.asarray(b)), k
        plan = zero1_bucket_plan(sa['m'].layout, 4)
        with mesh:
            ha = analyze_hlo(jax.jit(step_a).lower(
                params, init_a(params), batch).compile().as_text())
        budget = plan.max_grad_bucket_bytes
        live = ha['live_peak_reduce-scatter']
        print('ASYNC maxop', ha['maxop_reduce-scatter'], 'live', live,
              'budget', budget, 'overlap', ha['overlap_fraction'])
        assert ha['maxop_reduce-scatter'] <= budget
        assert live <= 2 * plan.grad_peak_bytes(4), (live, budget)
        assert ha['overlap_fraction'] > 0.0
    """, devices=4, timeout=1800)
    assert "PDIFF 0.0" in out
    assert "ASYNC maxop" in out


def test_dp2_tp2_manual_product_matches_flat_4dp():
    """Mesh composition acceptance: a (2, 2) 'data' x 'model' mesh with
    BOTH axes in the manual dp product (the composition whose arena
    kernels also compile on a TPU) is BITWISE
    identical to the flat 4-device dp mesh, async schedule included: the
    reduce-scatter ring order is the linearized axis product either way,
    and the ring all-gather's ppermute takes the same tuple of axis
    names."""
    out = run_sub("""
        import dataclasses, jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.configs import get_config, OptimizerConfig
        from repro.models.model import init_params
        from repro.core.dp_shardmap import make_dp_train_step
        cfg = dataclasses.replace(get_config('stablelm_1_6b').reduced(),
                                  compute_dtype='float32')
        params = init_params(cfg, jax.random.key(0))
        tokens = jax.random.randint(jax.random.key(1), (8, 16), 0, cfg.vocab_size)
        batch = {'tokens': tokens, 'labels': tokens}
        mesh4 = make_mesh((4,), ('data',))
        mesh22 = make_mesh((2, 2), ('data', 'model'))
        for azync in (False, True):
            oc = OptimizerConfig(name='adama', accumulation='adama',
                                 micro_batches=2, use_pallas=True, arena=True,
                                 zero_stage=1, zero_async=azync)
            step4, init4 = make_dp_train_step(cfg, oc, mesh4, ('data',), 'adama')
            step22, init22 = make_dp_train_step(cfg, oc, mesh22,
                                                ('data', 'model'), 'adama')
            with mesh4:
                p4, s4, m4 = jax.jit(step4)(params, init4(params), batch)
            with mesh22:
                p22, s22, m22 = jax.jit(step22)(params, init22(params), batch)
            pd = max(float(jnp.max(jnp.abs(a - b)))
                     for a, b in zip(jax.tree.leaves(p4), jax.tree.leaves(p22)))
            print('MESH22', 'async' if azync else 'serial', 'PDIFF', pd)
            assert pd == 0.0, (azync, pd)
            assert float(m4['loss']) == float(m22['loss'])
            for k in ('m', 'v'):
                for a, b in zip(jax.tree.leaves(s4[k]), jax.tree.leaves(s22[k])):
                    assert np.array_equal(np.asarray(a), np.asarray(b)), k
    """, devices=4, timeout=1800)
    assert "MESH22 serial PDIFF 0.0" in out
    assert "MESH22 async PDIFF 0.0" in out


def test_elastic_checkpoint_reshard_4_to_2_and_back():
    """Elastic resume: a checkpoint written by a 4-shard bucketed run
    restores as a 2-shard bucketed run (and back) BITWISE. The on-disk
    format is always canonical arena order (save unpermutes), and two
    shard counts' layouts differ only in zero tail padding, so
    restore(..., elastic=True) is a pure row-count negotiation — pad up
    with zeros, or truncate after proving the dropped tail IS zeros —
    then `bucket_plan=` re-permutes into the NEW plan's partition order.
    Without elastic=True the same restore refuses (treedef embeds the
    layout), and that refusal names the escape."""
    out = run_sub("""
        import dataclasses, tempfile, jax, jax.numpy as jnp, numpy as np
        import pytest
        from repro.launch.mesh import make_mesh
        from repro.configs import get_config, OptimizerConfig
        from repro.models.model import init_params
        from repro.core.dp_shardmap import make_dp_train_step
        from repro.core import buckets as buckets_mod
        from repro.core.zero import zero1_bucket_plan
        from repro.train import checkpoint
        cfg = dataclasses.replace(get_config('stablelm_1_6b').reduced(),
                                  compute_dtype='float32')
        params = init_params(cfg, jax.random.key(0))
        tokens = jax.random.randint(jax.random.key(1), (8, 16), 0, cfg.vocab_size)
        batch = {'tokens': tokens, 'labels': tokens}
        oc = OptimizerConfig(name='adama', accumulation='adama',
                             micro_batches=2, use_pallas=True, arena=True,
                             zero_stage=1)
        mesh4 = make_mesh((4,), ('data',))
        mesh2 = make_mesh((2,), ('data',), devices=jax.devices()[:2])
        step4, init4 = make_dp_train_step(cfg, oc, mesh4, ('data',), 'adama')
        step2, init2 = make_dp_train_step(cfg, oc, mesh2, ('data',), 'adama')
        with mesh4:
            p4, s4, _ = jax.jit(step4)(params, init4(params), batch)
        plan4 = zero1_bucket_plan(s4['m'].layout, 4)
        s2_ref = init2(params)
        plan2 = zero1_bucket_plan(s2_ref['m'].layout, 2)
        ckpt = tempfile.mkdtemp()
        checkpoint.save(ckpt, 1, s4, bucket_plan=plan4)
        # non-elastic restore onto the 2-shard layout refuses, naming the out
        try:
            checkpoint.restore(ckpt, 1, s2_ref, bucket_plan=plan2)
            raise SystemExit('expected a treedef/shape mismatch refusal')
        except ValueError as e:
            assert 'elastic=True' in str(e), e
        s2 = checkpoint.restore(ckpt, 1, s2_ref, bucket_plan=plan2,
                                elastic=True)
        canon4 = buckets_mod.unpermute_state(s4, plan4)
        canon2 = buckets_mod.unpermute_state(s2, plan2)
        for k in ('m', 'v'):
            t4 = canon4[k].to_tree(jnp.float32)
            t2 = canon2[k].to_tree(jnp.float32)
            for a, b in zip(jax.tree.leaves(t4), jax.tree.leaves(t2)):
                assert np.array_equal(np.asarray(a), np.asarray(b)), k
        assert int(s2['step']) == int(s4['step'])
        print('RESHARD 4to2 OK')
        # and back up: 2-shard checkpoint resumes as 4-shard (zero pad-up)
        ckpt2 = tempfile.mkdtemp()
        checkpoint.save(ckpt2, 1, s2, bucket_plan=plan2)
        s4b = checkpoint.restore(ckpt2, 1, s4, bucket_plan=plan4,
                                 elastic=True)
        canon4b = buckets_mod.unpermute_state(s4b, plan4)
        for k in ('m', 'v'):
            ta = canon4[k].to_tree(jnp.float32)
            tb = canon4b[k].to_tree(jnp.float32)
            for a, b in zip(jax.tree.leaves(ta), jax.tree.leaves(tb)):
                assert np.array_equal(np.asarray(a), np.asarray(b)), k
        # the resharded state TRAINS: one more step on the 2-shard mesh
        # (pull the 4-device-sharded params to host first — the 2-device
        # shard_map may not consume arrays committed to devices 2/3)
        p4h = jax.device_get(p4)
        with mesh2:
            p2b, s2b, _ = jax.jit(step2)(p4h, s2, batch)
        assert int(s2b['step']) == 2
        assert all(bool(jnp.all(jnp.isfinite(l)))
                   for l in jax.tree.leaves(p2b))
        print('RESHARD 2to4 OK')
    """, devices=4, timeout=1800)
    assert "RESHARD 4to2 OK" in out
    assert "RESHARD 2to4 OK" in out
