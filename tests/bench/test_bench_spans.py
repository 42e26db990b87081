"""The readers of the host outer loop's spans (``outer.idle_ms_per_batch``,
``outer.lowerings_per_batch`` and ``harness.spans``), on a hand-built trace
whose answers are known exactly, and on slices of traced chip windows that
hold whole ``outer/batch`` spans (``tests/bench/data/spans_*.json``, cut by
``bench/calibrate.py --trace-out --trace-ms``)."""
import random
import sys
from pathlib import Path

import pytest

from benchutil import BENCH, REPO

sys.path.insert(0, str(BENCH))
from harness import registry  # noqa: E402
from harness import spans as bspans  # noqa: E402
from harness.trace import (Device, Trace, clip, from_json, load,  # noqa: E402
                           to_json, union)

SLICES = sorted((Path(__file__).parent / "data").glob("spans_*.json"))
MS = 1e6                              # ns per ms
IDLE = "outer.idle_ms_per_batch"
LOWER = "outer.lowerings_per_batch"


class Run:
    def __init__(self, trace):
        self.trace, self.counters, self.peaks = trace, {}, None


def read(name, trace):
    return registry.metric(REPO, name).read(Run(trace))


def ev(name, a, b):
    return (name, a * MS, (b - a) * MS)


def hand_built(devices=True) -> Trace:
    """Two whole batches in a window of 1000 ms, and one cut by its end.
    Batch 0 [100, 500]: stage, init, sync; batch 1 [500, 900]: stage,
    init, then 50 ms under no step span."""
    host = [ev("outer/fit", 100, 900),
            ev("outer/batch", 100, 500), ev("outer/stage", 100, 200),
            ev("outer/init", 200, 400), ev("outer/sync", 400, 500),
            ev("outer/batch", 500, 900), ev("outer/stage", 500, 600),
            ev("outer/init", 600, 850),
            ev("outer/batch", 950, 1100), ev("outer/stage", 950, 1000)]
    host += [ev("lower_sharding_computation", t, t + 1)
             for t in (50, 150, 250, 300, 650, 870, 960)]
    host += [ev("PjitFunction(multiply)", 250, 260)]
    devs = [Device("/device:TPU:0", [ev("op", 0, 120), ev("op", 300, 450),
                                     ev("op", 600, 700)], []),
            Device("/device:TPU:1", [ev("op", 420, 520),
                                     ev("op", 880, 1000)], [])]
    return Trace((0.0, 1000 * MS), devs if devices else [], host)


def test_readers_on_a_hand_built_trace():
    tr = hand_built()
    # idle, averaged over the two devices: batch 0 (230 + 320) / 2 = 275,
    # batch 1 (300 + 360) / 2 = 330; the batch cut by the window is out
    assert read(IDLE, tr) == pytest.approx((275 + 330) / 2)
    # lowerings starting in a batch: 3 in batch 0, 2 in batch 1
    assert read(LOWER, tr) == 2.5
    want = {"outer/stage": (90.0, 0.5), "outer/init": (175.0, 1.5),
            "outer/sync": (17.5, 0.0), "(unnamed)": (20.0, 0.5)}
    for mod in (IDLE, LOWER):
        got = registry.metric(REPO, mod).by_phase(tr)
        assert set(got) == set(want)
        for name, (idle, low) in want.items():
            assert got[name][0] == pytest.approx(idle)
            assert got[name][1] == low
    assert bspans.Spans(tr).coverage() == [1.0, pytest.approx(0.875)]
    # a kept slice clips the batch its edge cuts: that batch is not whole
    cut = from_json(to_json(tr, lo=0.0, hi=700 * MS))
    assert bspans.Spans(cut).batches == [(100 * MS, 500 * MS)]
    assert read(LOWER, cut) == 3


def test_readers_without_devices_or_spans():
    bare = hand_built(devices=False)
    assert read(IDLE, bare) is None
    assert read(LOWER, bare) == 2.5
    assert bspans.by_phase(bare)["outer/init"] == (None, 1.5)
    # a program without the outer loop's spans (the parent of this
    # metric) reads nothing and raises nothing
    tr = hand_built()
    plain = Trace(tr.window, tr.devices,
                  [e for e in tr.host if not e[0].startswith("outer/")])
    assert read(IDLE, plain) is None and read(LOWER, plain) is None
    assert bspans.by_phase(plain) == {}
    for name in (IDLE, LOWER):
        assert registry.metric(REPO, name).read(
            type("R", (), {"trace": None, "counters": {}})) is None


def plain_idle_ns(trace, lo, hi):
    return (hi - lo) - sum(
        sum(b - a for a, b in union(clip(d.ops, lo, hi)))
        for d in trace.devices) / len(trace.devices)


def test_range_sums_match_a_plain_sweep():
    rng = random.Random(7)
    ops = []
    for _ in range(300):
        a = rng.uniform(0, 1000)
        ops.append(("op", a, rng.uniform(0, 8)))
    tr = Trace((0.0, 1000.0), [Device("d0", ops, []),
                               Device("d1", ops[::2], [])], [])
    sp = bspans.Spans(tr)
    for _ in range(300):
        lo = rng.uniform(0, 800)
        hi = lo + rng.uniform(0, 200)
        assert sp.idle_ns(lo, hi) == pytest.approx(
            plain_idle_ns(tr, lo, hi), abs=1e-9)


def test_spans_slices_were_recorded_on_a_tpu():
    assert {p.name for p in SLICES} == {"spans_restarts.json",
                                        "spans_restarts4.json"}
    for p in SLICES:
        tr = load(str(p))
        assert tr.devices and all("TPU" in d.name for d in tr.devices)
        assert p.stat().st_size < 1_000_000


@pytest.mark.parametrize("path", SLICES, ids=[p.stem for p in SLICES])
def test_readers_on_chip_slices(path):
    tr = load(str(path))
    sp = bspans.Spans(tr)
    assert sp.batches, "the slice holds no whole outer/batch span"
    idle = [plain_idle_ns(tr, a, b) for a, b in sp.batches]
    low = [sum(1 for n, s, _ in tr.host
               if n == "lower_sharding_computation" and a <= s <= b)
           for a, b in sp.batches]
    assert read(IDLE, tr) == pytest.approx(sum(idle) / len(idle) / MS)
    assert read(LOWER, tr) == sum(low) / len(low)
    assert read(LOWER, tr) > 0
    phases = bspans.by_phase(tr)
    steps = {"outer/stage", "outer/landmarks", "outer/diag", "outer/seed",
             "outer/init", "outer/inner", "outer/merge", "outer/sync",
             "outer/checkpoint", "(unnamed)"}
    assert set(phases) <= steps and "outer/inner" in phases
    assert sum(v[0] for v in phases.values()) == \
        pytest.approx(read(IDLE, tr))
    assert sum(v[1] for v in phases.values()) == \
        pytest.approx(read(LOWER, tr))
    assert min(sp.coverage()) >= 0.95
