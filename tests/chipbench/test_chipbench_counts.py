"""The benchmark's FLOP, parameter and byte counters against hand counts."""
import json

import pytest
from _paths import ROOT

from chipbench import counts
from chipbench.peaks import peaks_for


def _cfg(name):
    return json.loads((ROOT / "chipbench" / "configs" / f"{name}.json").read_text())


def test_mlp_forward_flops_and_params():
    cfg = _cfg("paper_mlp")
    # 2·(784·512 + 512·256 + 256·128 + 128·10)
    assert counts.forward_flops(cfg) == 1_133_056
    assert counts.params_per_node(cfg) == cfg["params_per_node"] == 567_434


def test_train_flops_per_node_round():
    cfg = _cfg("paper_mlp")
    tr = {"local_batches": 8, "batch_size": 16}
    # 3 × forward × 128 samples ≈ 435 MFLOP per node-round
    assert counts.train_flops_per_node_round(cfg, tr) == 3 * 1_133_056 * 128


def test_mix_bound_ba256_mlp():
    peaks = peaks_for("TPU v5 lite")
    n, d, nnz = 256, 567_434, 4024
    assert counts.mix_bytes(n, d) == 2 * 256 * 567_434 * 4  # 1.162 GB
    assert counts.mix_flops(n, d, nnz) == 2 * (4024 + 256) * 567_434
    # bandwidth-bound: 1.162 GB at 819 GB/s
    assert counts.mix_least_seconds(n, d, nnz, peaks) == pytest.approx(1.162e9 / 819e9, rel=1e-3)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(ValueError, match="no published peaks"):
        peaks_for("TPU v9 imaginary")
