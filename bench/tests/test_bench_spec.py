"""A configuration file's `model` block against the program's ModelConfig,
nested blocks (`moe`) included, and nested `model_overrides`."""
import dataclasses
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from benchkit import spec  # noqa: E402

MOE = {"n_experts": 64, "n_shared": 2, "top_k": 6, "d_expert": 1408,
       "dense_prefix": 1}


def _cell(model, overrides=None):
    config = {"train_args": ["--arch", "deepseek-v2-lite-16b"],
              "model": model}
    if overrides:
        config["model_overrides"] = overrides
    return spec.Cell(name="deepseek_v2_lite.test", chips=1,
                     config_name="deepseek_v2_lite", traffic_name="test",
                     config=config,
                     traffic={"train_args": [], "seq_len": 128,
                              "global_batch": 4, "micro_batches": 2},
                     limits={}, end_to_end=[], per_layer=[])


def _program():
    from repro.configs import get_config
    return get_config("deepseek-v2-lite-16b")


def test_nested_moe_block_that_matches_passes():
    spec.check_config(_cell({"num_layers": 27, "attention": "mla",
                             "kv_lora_rank": 512, "moe": MOE}), _program())


@pytest.mark.parametrize("block", [
    dict(MOE, top_k=8),
    dict(MOE, d_expert=1024),
    dict(MOE, n_experts_held=8),        # a setting the program lacks
])
def test_nested_moe_block_that_differs_raises(block):
    with pytest.raises(spec.SpecError, match="moe"):
        spec.check_config(_cell({"num_layers": 27, "moe": block}),
                          _program())


def test_nested_block_where_the_program_has_none_raises():
    from repro.configs import get_config
    with pytest.raises(spec.SpecError, match="moe"):
        spec.check_config(_cell({"moe": MOE}), get_config("stablelm-1.6b"))


def test_nested_override_replaces_only_its_keys():
    cell = _cell({"num_layers": 27, "moe": dict(MOE, n_experts=8)},
                 overrides={"moe": {"n_experts": 8}})
    run = spec.build_run(cell, 1)
    want = dataclasses.replace(_program().moe, n_experts=8)
    assert run.model.moe == want
    assert run.model.d_ff == _program().d_ff


def test_nested_override_is_checked():
    cell = _cell({"num_layers": 27, "moe": MOE},
                 overrides={"moe": {"n_experts": 8}})
    with pytest.raises(spec.SpecError, match="moe.n_experts"):
        spec.build_run(cell, 1)
