"""Operation and byte counts against hand values for pubmed."""

import pytest

from bench import work

# pubmed at Table III size: 19,717 nodes; the synthesized graph has 90,838
# stored (symmetric) edges, plus one self loop per node.
N, NNZ, DIMS = 19_717, 90_838 + 19_717, [500, 16, 3]
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_pubmed_forward_flops():
    comb = 2 * 19_717 * 500 * 16 + 2 * 19_717 * 16 * 3
    assert work.combination_flops(N, DIMS) == comb == 317_364_832
    agg = 2 * 110_555 * 16 + 2 * 110_555 * 3
    assert agg == 4_201_090
    assert work.forward_flops(N, NNZ, DIMS) == comb + agg == 321_565_922


def test_pubmed_aggregation_bytes():
    # values + column indices once, dense rows read once, output once
    assert work.aggregation_bytes(N, NNZ, 16) == 110_555 * 8 \
        + 2 * 19_717 * 16 * 4 == 3_408_216
    assert work.aggregation_bytes(N, NNZ, 3) == 1_357_648


def test_pubmed_aggregation_least_time_is_bandwidth_bound():
    least = work.aggregation_least_s(N, NNZ, DIMS, PEAKS)
    assert least == pytest.approx((3_408_216 + 1_357_648) / 819e9)
    assert least == pytest.approx(5.8191e-6, rel=1e-4)
