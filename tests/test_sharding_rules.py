"""Sharding rules: every leaf spec must be divisibility-consistent for every
arch on the production mesh topology (checked abstractly, no devices)."""
import jax
import numpy as np
import pytest

from repro.configs import ARCH_IDS, INPUT_SHAPES, get_config, shape_supported
from repro.models.decode import abstract_cache
from repro.models.model import abstract_params


class FakeMesh:
    """Duck-typed mesh: Rules only reads .shape (a dict)."""
    def __init__(self, shape):
        self.shape = shape


from repro.sharding.rules import Rules  # noqa: E402


def _check_tree(specs, tree, mesh_shape, what):
    flat_s = jax.tree.leaves(specs, is_leaf=lambda x: hasattr(x, "index"))
    flat_t = jax.tree.leaves(tree)
    assert len(flat_s) == len(flat_t), what
    for spec, leaf in zip(flat_s, flat_t):
        entries = tuple(spec)
        assert len(entries) <= leaf.ndim, (what, spec, leaf.shape)
        for i, ax in enumerate(entries):
            if ax is None:
                continue
            axes = ax if isinstance(ax, tuple) else (ax,)
            size = int(np.prod([mesh_shape[a] for a in axes]))
            assert leaf.shape[i] % size == 0, \
                f"{what}: dim {i} of {leaf.shape} not divisible by {size} ({spec})"


MESHES = [{"data": 16, "model": 16},
          {"pod": 2, "data": 16, "model": 16}]


@pytest.mark.parametrize("arch", ARCH_IDS)
@pytest.mark.parametrize("mesh_shape", MESHES, ids=["1pod", "2pod"])
def test_param_and_opt_specs_divisible(arch, mesh_shape):
    cfg = get_config(arch)
    mesh = FakeMesh(mesh_shape)
    rules = Rules(cfg, mesh, fsdp=True)
    aparams = abstract_params(cfg, tp=mesh_shape["model"])
    pspecs = rules.params_pspecs(aparams)
    _check_tree(pspecs, aparams, mesh_shape, f"{arch} params")


@pytest.mark.parametrize("arch", ["stablelm_1_6b", "deepseek_v2_236b",
                                  "rwkv6_7b", "hymba_1_5b", "whisper_base"])
def test_cache_specs_divisible(arch):
    cfg = get_config(arch)
    mesh_shape = MESHES[0]
    mesh = FakeMesh(mesh_shape)
    rules = Rules(cfg, mesh, fsdp=True)
    for sname in ("decode_32k", "long_500k"):
        shape = INPUT_SHAPES[sname]
        ok, _ = shape_supported(cfg, shape)
        if not ok:
            continue
        cache = abstract_cache(cfg, shape.global_batch, shape.seq_len)
        cspecs = rules.cache_pspecs(cache)
        _check_tree(cspecs, cache, mesh_shape, f"{arch} {sname} cache")


def test_vocab_padding_is_tp_divisible():
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        assert cfg.padded_vocab(16) % (128 * 16) == 0
        assert cfg.padded_vocab(16) >= cfg.vocab_size


def test_zero1_adds_data_axis():
    from repro.core.zero import _add_axis
    from jax.sharding import PartitionSpec as P
    mesh = FakeMesh({"data": 16, "model": 16})
    spec = _add_axis(P(None, "model"), (4096, 1024), mesh, "data")
    assert spec == P("data", "model")
    # non-divisible dims stay unsharded
    spec = _add_axis(P(), (17, 33), mesh, "data")
    assert spec == P(None, None)


# ---------------------------------------------------------------------------
# zero1_state_sharding edge cases (per-leaf ZeRO-1 over an abstract mesh)
# ---------------------------------------------------------------------------


def _abstract_mesh(shape):
    return jax.sharding.AbstractMesh(tuple(shape.values()), tuple(shape))


def _zero1(mesh, psh, aparams):
    from repro.core.zero import zero1_state_sharding
    return zero1_state_sharding(psh, aparams, mesh)


def test_zero1_no_divisible_dim_stays_replicated():
    """A leaf with no dim divisible by the data-axis size must come back
    with its ORIGINAL spec — sharding it would fail at compile time."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = _abstract_mesh({"data": 16, "model": 2})
    ap = {"odd": jax.ShapeDtypeStruct((17, 33), np.float32)}
    mv = _zero1(mesh, {"odd": NamedSharding(mesh, P())}, ap)
    assert mv["odd"].spec == P(None, None)


def test_zero1_already_fully_sharded_spec_unchanged():
    """Every dim already carries a mesh axis: nothing left to shard; the
    spec must pass through untouched (not doubled, not reordered)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = _abstract_mesh({"data": 16, "model": 2})
    ap = {"w": jax.ShapeDtypeStruct((64, 32), np.float32)}
    mv = _zero1(mesh, {"w": NamedSharding(mesh, P("data", "model"))}, ap)
    assert mv["w"].spec == P("data", "model")


def test_zero1_scalar_leaf_stays_replicated():
    """0-d leaves (step counters, scales) have no dim to shard."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = _abstract_mesh({"data": 16, "model": 2})
    ap = {"step": jax.ShapeDtypeStruct((), np.int32)}
    mv = _zero1(mesh, {"step": NamedSharding(mesh, P())}, ap)
    assert mv["step"].spec == P()


def test_zero1_picks_largest_divisible_unsharded_dim():
    """Mixed tree: the data axis lands on the LARGEST divisible dim that is
    not already taken, per leaf, independently."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = _abstract_mesh({"data": 16, "model": 2})
    ap = {
        "emb": jax.ShapeDtypeStruct((50304, 1024), np.float32),
        "qkv": jax.ShapeDtypeStruct((1024, 3072), np.float32),
        "bias": jax.ShapeDtypeStruct((640,), np.float32),
    }
    psh = {
        "emb": NamedSharding(mesh, P(None, "model")),
        "qkv": NamedSharding(mesh, P("model", None)),
        "bias": NamedSharding(mesh, P()),
    }
    mv = _zero1(mesh, psh, ap)
    assert mv["emb"].spec == P("data", "model")    # 50304 > 1024
    assert mv["qkv"].spec == P("model", "data")    # dim 0 taken -> dim 1
    assert mv["bias"].spec == P("data")            # 640 % 16 == 0


def test_zero1_accepts_raw_pspec_leaves():
    """The sharding tree may carry bare PartitionSpecs (pre-NamedSharding
    rules output); the result is still NamedSharding on the given mesh."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = _abstract_mesh({"data": 4})
    ap = {"w": jax.ShapeDtypeStruct((8, 3), np.float32)}
    mv = _zero1(mesh, {"w": P()}, ap)
    assert isinstance(mv["w"], NamedSharding)
    assert mv["w"].spec == P("data", None)


def test_opt_pspecs_covers_extra_arena_regions():
    """Regression: the arena branch of opt_pspecs must handle EVERY state
    key — the master-param region "p", the fp8 error-feedback residual
    "ef", the bf16 working-param cache "wp" (all row-indexed arena regions
    that shard like the moments), and unknown extras such as loss-scaler
    scalars (replicated). An fp8+master+wp state used to KeyError on "ef"
    because the comprehension only knew "step", "p", and the codec mask."""
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.core import adama

    params = {"w": jnp.zeros((256, 128), jnp.float32),
              "b": jnp.zeros((128,), jnp.float32)}
    st = adama.init_arena(params, n_shards=16, master_params=True,
                          error_feedback=True, work_param_cache=True)
    st["scaler"] = {"scale": jnp.float32(65536.0),
                    "good_steps": jnp.int32(0)}
    mesh = FakeMesh({"data": 16, "model": 16})
    rules = Rules(get_config("stablelm_1_6b"), mesh)

    specs = rules.opt_pspecs(st, params, zero1=True)
    assert set(specs) == set(st)
    row = P(("data",), None)
    assert specs["step"] == P()
    for region in ("p", "ef", "wp"):
        leaves = jax.tree.leaves(specs[region],
                                 is_leaf=lambda x: isinstance(x, P))
        assert leaves and all(s == row for s in leaves), (region, leaves)
    # moments follow the codec's row-indexed column mask (fp32: all rows)
    for mom in ("m", "v"):
        leaves = jax.tree.leaves(specs[mom],
                                 is_leaf=lambda x: isinstance(x, P))
        assert leaves and all(s == row for s in leaves), (mom, leaves)
    # unknown extra keys (scaler scalars) stay replicated
    sc = jax.tree.leaves(specs["scaler"],
                         is_leaf=lambda x: isinstance(x, P))
    assert sc and all(s == P() for s in sc)
