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
