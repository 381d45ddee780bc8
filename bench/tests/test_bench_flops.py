"""The benchmark's operation and byte counts against hand counts, and the
table of peaks."""
import json
import sys
from pathlib import Path

import jax
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from benchkit import flops, peaks  # noqa: E402

BERT_ROWS = 357_888          # bert_large's arena: rows of 1024 lanes


def _model(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())["model"]


def test_bert_large_flops_per_token_by_hand():
    d, f, s, v, layers = 1024, 4096, 512, 30522, 24
    per_layer = 2 * 4 * d * d + 2 * 2 * d * f + 2 * 2 * d * s
    want = 3 * (layers * per_layer + 2 * d * v)
    assert flops.train_flops_per_token(_model("bert_large"), s) == want
    assert want == pytest.approx(2.15e9, rel=0.01)


def test_stablelm_flops_per_token_by_hand():
    d, f, s, v, layers = 2048, 5632, 2048, 100352, 24
    per_layer = 2 * 4 * d * d + 2 * 3 * d * f + 2 * 2 * d * (s + 1) / 2
    want = 3 * (layers * per_layer + 2 * d * v)
    assert flops.train_flops_per_token(_model("stablelm_1_6b"), s) == want
    assert want == pytest.approx(9.23e9, rel=0.01)


# DeepSeek-V2-Lite's published sizes (hf:deepseek-ai/DeepSeek-V2-Lite,
# config.json) under the program's ModelConfig names
DEEPSEEK_V2_LITE = {
    "arch_type": "moe", "num_layers": 27, "d_model": 2048, "n_heads": 16,
    "n_kv_heads": 16, "head_dim": 128, "d_ff": 10944, "vocab_size": 102400,
    "act": "silu", "attention": "mla", "q_lora_rank": None,
    "kv_lora_rank": 512, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "v_head_dim": 128,
    "moe": {"n_experts": 64, "n_shared": 2, "top_k": 6, "d_expert": 1408,
            "dense_prefix": 1}}


def test_deepseek_v2_lite_layer_flops_by_hand():
    d, h, dn, dr, dv, r, s = 2048, 16, 128, 64, 128, 512, 4096
    part = flops.layer_flops(DEEPSEEK_V2_LITE, s)
    # q 12,582,912 + kv_a 2,359,296 + kv_b 4,194,304 + o 8,388,608
    assert part["proj"] == 27_525_120
    assert part["attn"] == 2 * h * (dn + dr + dv) * (s + 1) / 2
    assert part["mlp"] == 134_479_872
    assert part["router"] == 262_144
    assert part["shared"] == 34_603_008
    assert part["routed"] == 103_809_024
    assert (d, r) == (DEEPSEEK_V2_LITE["d_model"],
                      DEEPSEEK_V2_LITE["kv_lora_rank"])


def test_deepseek_v2_lite_flops_per_token_by_hand():
    s = 4096
    attn = 2 * 16 * (128 + 64 + 128) * (s + 1) / 2
    head = 419_430_400
    assert 2 * 2048 * 102400 == head
    want = 3 * (27 * (27_525_120 + attn) + 134_479_872
                + 26 * (262_144 + 34_603_008 + 103_809_024) + head)
    assert flops.train_flops_per_token(DEEPSEEK_V2_LITE, s) == want


def test_moe_counts_only_the_experts_held():
    held = dict(DEEPSEEK_V2_LITE,
                moe=dict(DEEPSEEK_V2_LITE["moe"], n_experts_held=8))
    part = flops.layer_flops(held, 2048)
    assert part["routed"] == 103_809_024 * 8 / 64
    assert part["router"] == 262_144        # routes over all 64


def test_mla_with_a_q_latent_by_hand():
    d, h, rq, dn, dr = 2048, 16, 768, 128, 64
    with_q = dict(DEEPSEEK_V2_LITE, q_lora_rank=rq)
    got = flops.layer_flops(with_q, 2048)["proj"]
    assert got == 27_525_120 - 2 * d * h * (dn + dr) \
        + 2 * d * rq + 2 * rq * h * (dn + dr)


def test_deepseek_keys_are_the_program_config_fields():
    from repro.configs import get_config
    cfg = get_config("deepseek-v2-lite-16b")
    block = {k: getattr(cfg, k) for k in DEEPSEEK_V2_LITE if k != "moe"}
    block["moe"] = {k: getattr(cfg.moe, k) for k in DEEPSEEK_V2_LITE["moe"]}
    assert block == DEEPSEEK_V2_LITE


def test_bert_large_arena_rows_match_the_program_layout():
    from repro.configs import get_config
    from repro.core import arena
    from repro.models.model import init_params
    cfg = get_config("bert_large")
    layout = arena.build_layout(jax.eval_shape(
        lambda: init_params(cfg, jax.random.key(0))))
    assert layout.rows == BERT_ROWS


@pytest.mark.parametrize("m_codec,v_codec,wire,per_elem", [
    ("fp32", "fp32", "fp32", 20),     # read m v g, write m v: 4+4+4+4+4
    ("fp32", "fp32", "bf16", 18),
])
def test_fold_bytes_bert_large_by_hand(m_codec, v_codec, wire, per_elem):
    assert flops.fold_bytes(BERT_ROWS, m_codec, v_codec, wire) \
        == BERT_ROWS * 1024 * per_elem


def test_fold_bytes_int8_codecs_by_hand():
    # int8 codes (1 B/elem) plus one fp32 scale per row, for m and for v,
    # read and written; a bf16 gradient read
    rows = 401_920
    want = rows * (2 * (1024 + 4) * 2 + 1024 * 2)
    assert flops.fold_bytes(rows, "int8", "int8", "bf16") == want


def test_apply_bytes_by_hand():
    # read p, m, v and write p: 4 + 4 + 4 + 4 bytes per element
    assert flops.apply_bytes(BERT_ROWS, "fp32", "fp32") \
        == BERT_ROWS * 1024 * 16
    assert flops.apply_bytes(BERT_ROWS, "fp32", "fp32", emit_work=True) \
        == BERT_ROWS * 1024 * 18


def test_peaks_are_keyed_by_device_kind():
    p = peaks.peaks_for("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for("cpu")
