"""FLOP counts of the yardstick against hand counts, and the table of
peaks."""
import pytest

from chipbench import harness
from chipbench.references import split_nn as ref


def _model(bottom, top=("mlp:hidden=8,final_act=0",)):
    return ref.Model({"data": {"master_features": 20, "member_features": [12],
                               "n_items": 3},
                      "towers": {"bottom": list(bottom), "top": list(top)},
                      "embedding_dim": 4, "lr": 0.1})


def test_mlp_config_flops_per_sample_by_hand():
    m = _model(["mlp:hidden=6"])
    # master 20->6->4, member 12->6->4, top 4->8->3: 2 per multiply-add
    fwd = 2 * (20 * 6 + 6 * 4) + 2 * (12 * 6 + 6 * 4) + 2 * (4 * 8 + 8 * 3)
    assert m.train_flops_per_sample() == 3 * fwd


def test_table1_mlp_flops_per_sample():
    cell = harness.load_cell("recsys-table1-mlp.train")
    m = cell.reference.Model(cell.config)
    fwd = 2 * (1345 * 256 + 256 * 128) + 2 * (381 * 256 + 256 * 128) \
        + 2 * (128 * 128 + 128 * 64 + 64 * 19)
    assert m.train_flops_per_sample() == 3 * fwd


def test_peaks_known_kind():
    p = harness.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9


def test_peaks_unknown_kind_is_an_error():
    with pytest.raises(KeyError, match="not in peaks.json"):
        harness.peaks("TPU v9 imaginary")
