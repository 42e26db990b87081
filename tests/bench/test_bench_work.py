"""The Gram engine's roofline arithmetic against hand counts."""
import sys

import pytest

from benchutil import BENCH, REPO

sys.path.insert(0, str(BENCH))
from harness import registry  # noqa: E402

roof = registry.metric(REPO, "gram_roofline")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_work_by_hand():
    # one Gram of 4 x 3 over 2 features: 2*4*3*2 = 48 multiply-adds,
    # 4 elementwise rbf steps on 12 entries = 48, then 5 iterations of
    # [4, 3] x [3, 2] products: 5 * 2*4*3*2 = 240
    assert roof.work(4, 3, 2, 2, 5) == 48 + 48 + 240


def test_traffic_by_hand():
    assert roof.traffic(4, 3, 2) == 4 * (4 + 3) * 2


def test_least_time_at_tab1_shape():
    b = {"rows": 35000, "landmarks": 35000, "dim": 784, "clusters": 10,
         "inner_iters": 10}
    ops = 2 * 35000 ** 2 * 784 + 4 * 35000 ** 2 + 10 * 2 * 35000 ** 2 * 10
    assert roof.least_seconds(b, 1, PEAKS) == pytest.approx(ops / 197e12)
    # on four chips each device holds a quarter of the rows
    assert roof.least_seconds(b, 4, PEAKS) == pytest.approx(
        ops / 4 / 197e12)


def test_bandwidth_bound_when_little_work():
    b = {"rows": 8, "landmarks": 8, "dim": 1 << 20, "clusters": 10,
         "inner_iters": 1}
    assert roof.least_seconds(b, 1, PEAKS) == pytest.approx(
        4.0 * 16 * (1 << 20) / 819e9)
