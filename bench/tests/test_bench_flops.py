"""Operations and bytes from shapes (`bench/flops.py`) against hand counts,
and the table of peaks."""
import pytest

import cells  # noqa: F401  (puts the harness on the path)
import flops
import run


def test_gnn_mp_counts_two_products_the_aggregation_and_the_epilogue():
    # B=2 graphs of N=3 nodes, F=4 -> Fo=5
    ops, data = flops.gnn_mp(2, 3, 4, 5)
    assert ops == 2 * (2 * 2 * 3 * 4 * 5) + 2 * 2 * 3 * 3 * 5 + 3 * 2 * 3 * 5
    assert ops == 750
    # adjacency 18, features 24, two panels 40, bias 5, output 30 floats
    assert data == 4 * (18 + 24 + 40 + 5 + 30) == 468


def test_engine_chunk_runs_every_layer_of_both_stages():
    calls = flops.engine_chunk_calls(2, 3, 4, 5, 2)
    assert calls == [flops.gnn_mp(2, 3, 4, 5), flops.gnn_mp(2, 3, 5, 5)] * 2


def test_forward_flops_by_hand():
    # 2 nodes, 3 features, hidden 4, one layer, 4 targets
    stack = 2 * 2 * 2 * 3 + 2 * 3 + 2 * 2 * 2 * 3 * 4 + 3 * 2 * 4      # 150
    stage1 = stack + 2 * 2 * 4 * 4 + 2 * 2 * 4 + 2 * 2 * 4 + 2         # 248
    stage2 = stack + 2 * 2 * 4 + 2 * 2 * 4 * 4 + 2 * 4 + 2 * 4 * 4 + 4  # 274
    assert flops.forward_flops(2, 3, 4, 1) == stage1 + stage2 == 522


def test_peaks_are_those_published_for_v5e():
    pk = run.peaks("TPU v5 lite")
    assert pk["bf16_flops_per_s"] == 197e12
    assert pk["hbm_bytes_per_s"] == 819e9


def test_unknown_device_has_no_default_peak():
    with pytest.raises(KeyError):
        run.peaks("TPU v9 imaginary")
