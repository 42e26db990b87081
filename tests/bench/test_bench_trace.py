"""The trace reductions, read on slices of traces recorded on the chip
(``tests/bench/data/trace_*.json``: a few ms of a traced window, kept as
plain events by ``bench/calibrate.py --trace-out``). Each reduction is
held against a second, plain computation over the same events."""
import sys
from pathlib import Path

import pytest

from benchutil import BENCH, REPO

sys.path.insert(0, str(BENCH))
from harness import registry  # noqa: E402
from harness import trace as btrace  # noqa: E402

TRACES = sorted((Path(__file__).parent / "data").glob("trace_*.json"))
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def covered_ns(events, lo, hi) -> float:
    """Length of [lo, hi] covered by at least one event, by a sweep over
    the event boundaries."""
    edges = []
    for _, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            edges += [(a, 1), (b, -1)]
    edges.sort()
    total, depth, last = 0.0, 0, None
    for t, step in edges:
        if depth > 0:
            total += t - last
        depth += step
        last = t
    return total


class Run:
    def __init__(self, trace, counters):
        self.trace, self.counters, self.peaks = trace, counters, PEAKS


@pytest.fixture(params=TRACES, ids=[p.stem for p in TRACES])
def recorded(request):
    return btrace.load(str(request.param))


def test_traces_were_recorded_on_a_tpu():
    assert TRACES
    for p in TRACES:
        tr = btrace.load(str(p))
        assert tr.devices and all("TPU" in d.name for d in tr.devices)
        assert any(d.ops for d in tr.devices)


def test_busy_and_idle_share(recorded):
    lo, hi = recorded.window
    want = sum(covered_ns(d.ops, lo, hi) for d in recorded.devices) \
        / len(recorded.devices) * 1e-9
    assert btrace.busy_s(recorded) == pytest.approx(want, rel=1e-9)
    share = btrace.idle_share(recorded)
    assert 0.0 <= share <= 100.0
    assert share == pytest.approx(100 * (1 - want / recorded.window_s))
    idle = registry.metric(REPO, "device.idle_share.fit").read(
        Run(recorded, {}))
    assert idle == share


def test_top_ops_and_idle_gaps(recorded):
    lo, hi = recorded.window
    ops = btrace.top_ops(recorded)
    assert 0 < len(ops) <= 10
    assert [s for _, s in ops] == sorted((s for _, s in ops), reverse=True)
    total = sum(d for dev in recorded.devices
                for _, _, d in btrace.clip(dev.ops, lo, hi))
    assert sum(s for _, s in ops) <= total * 1e-9 / len(recorded.devices) \
        * (1 + 1e-9)
    gaps = btrace.idle_gaps(recorded)
    idle0 = (hi - lo - covered_ns(recorded.devices[0].ops, lo, hi)) * 1e-9
    assert sum(s for _, s in gaps) <= idle0 * (1 + 1e-9)
    if len(gaps) < 10:
        assert sum(s for _, s in gaps) == pytest.approx(idle0, rel=1e-6)


def test_launches_per_batch(recorded):
    lo, hi = recorded.window
    n = sum(1 for _, s, d in recorded.devices[0].modules
            if s + d > lo and s < hi)
    read = registry.metric(REPO, "outer.launches_per_batch").read
    assert read(Run(recorded, {"batches": [{}, {}]})) == n / 2
    assert read(Run(recorded, {"batches": []})) is None


def test_inner_program_readers(recorded):
    inner = [[e for e in d.modules if "_mesh_program" in e[0]]
             for d in recorded.devices]
    batch = {"rows": 35000, "landmarks": 35000, "dim": 784, "clusters": 10,
             "inner_iters": 3}
    roof = registry.metric(REPO, "gram_roofline").read(
        Run(recorded, {"batches": [batch], "chips": len(recorded.devices)}))
    coll = registry.metric(REPO, "inner.collective_share").read(
        Run(recorded, {}))
    if not any(inner):
        assert roof is None
        return
    assert roof > 0
    if len(recorded.devices) > 1:
        assert 0.0 <= coll <= 100.0
    else:
        assert coll is None
