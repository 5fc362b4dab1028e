"""The reduction from a profiler trace to the benchmark's numbers: on
intervals built by hand, and on small traces recorded on the chip."""
import pathlib

import pytest

from chipbench import trace

FIXTURES = pathlib.Path(__file__).parent / "fixtures"


def _dev(ops, modules=()):
    return trace.Device(0, list(ops), list(modules))


def test_union_and_covered():
    merged = trace.union([(5, 7, "b"), (0, 2, "a"), (1, 3, "c"), (9, 10)])
    assert merged == [(0, 3), (5, 7), (9, 10)]
    assert trace.covered(merged, 2, 9.5) == 1 + 2 + 0.5


def test_busy_counts_nested_and_overlapping_ops_once():
    dev = _dev([(0, 10, "while.1"), (1, 4, "fusion.2"), (12, 14, "copy.3")])
    assert trace.busy_ns(dev, 0, 20) == 12
    assert trace.busy_ns(dev, 5, 13) == 6


def test_module_time_by_name_and_renamed_module_reads_none():
    dev = _dev([], [(0, 4, "jit_mcm"), (6, 9, "jit_mcm"), (9, 10, "jit_x")])
    assert trace.module_ns(dev, ["jit_mcm"], 0, 20) == 7
    assert trace.module_ns(dev, ["jit_mcm"], 2, 7) == 3
    assert trace.module_ns(dev, ["jit_mcm_v2"], 0, 20) is None


def test_collectives():
    dev = _dev([(0, 5, "fusion.1"), (1, 2, "all-gather.3"),
                (1.5, 3, "all-to-all.2"), (6, 7, "all-reduce-start.1")])
    assert trace.collective_ns(dev, 0, 10) == 3
    assert trace.collective_ns(_dev([(0, 5, "fusion.1")]), 0, 10) is None


def test_self_times_take_nested_ops_out():
    dev = _dev([(0, 10, "while.1"), (1, 4, "fusion.2"), (5, 6, "fusion.2")],
               [(0, 10, "jit_mcm")])
    assert trace.self_times(dev, 0, 10) == {"jit_mcm/while.1": 6,
                                            "jit_mcm/fusion.2": 4}


def test_idle_gaps_named_by_the_open_span():
    spans = [(0, 2, "host-in"), (2, 9, "solve"), (9, 10, "mates-out"),
             (12, 14, "host-in")]
    dev = _dev([(3, 5, "a"), (6, 8, "b")])
    # gaps [0, 3], [5, 6], [8, 14], each named at its middle
    assert trace.idle_gaps(dev, spans, 0, 14) == [
        ("between-spans", 6), ("host-in", 3), ("solve", 1)]


def test_steps_and_window():
    tr = trace.Trace({}, [(0, 2, "host-in"), (2, 9, "solve"),
                          (9, 10, "mates-out"), (12, 13, "host-in"),
                          (13, 20, "solve"), (20, 21, "mates-out")])
    assert tr.window == (0, 21)
    assert tr.steps() == [(0, 10), (12, 21)]


@pytest.fixture(scope="module")
def one_chip():
    """Two solves of the one-chip route at n = 1,024, traced on a v5e in
    the harness's spans."""
    return trace.load(FIXTURES / "one-chip.xplane.pb")


def test_recorded_one_chip_trace(one_chip):
    tr = one_chip
    assert sorted(tr.devices) == [0]
    assert [s[2] for s in tr.spans] == list(trace.SPANS) * 2
    assert tr.steps() == [(41068029.0, 115070897.0),
                          (115116787.0, 192166425.0)]
    lo, hi = tr.window
    dev = tr.devices[0]
    assert len(dev.ops) == 4459 and len(dev.modules) == 362
    assert trace.busy_ns(dev, lo, hi) == 43997462.0
    assert trace.module_ns(dev, ["jit_greedy_maximal"], lo, hi) == 7062242.0
    assert trace.module_ns(dev, ["jit_mcm"], lo, hi) == 23973589.0
    assert trace.module_ns(dev, ["jit__awac_loop"], lo, hi) == 12591868.0
    assert trace.collective_ns(dev, lo, hi) is None
    assert trace.phase_ms(tr, ["jit_mcm"]) == pytest.approx(23973589.0 / 2e6)
    assert trace.phase_ms(tr, ["jit_mcm_v2"]) is None
    assert trace.host_ms(tr) == pytest.approx(53.527522)
    assert trace.idle_pct(tr) == pytest.approx(70.8815823564401)


def test_recorded_self_times_add_up_to_busy(one_chip):
    lo, hi = one_chip.window
    dev = one_chip.devices[0]
    self_ns = trace.self_times(dev, lo, hi)
    assert sum(self_ns.values()) == pytest.approx(trace.busy_ns(dev, lo, hi))
    top = max(self_ns, key=self_ns.get)
    assert top.startswith(("jit_mcm/", "jit__awac_loop/"))
    gaps = trace.idle_gaps(dev, one_chip.spans, lo, hi)
    assert gaps[0] == ("mates-out", 3268336.0)
    assert {name for name, _ in gaps} <= set(trace.SPANS) | {"between-spans"}
