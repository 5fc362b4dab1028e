"""The AWAC sweep's byte count and its roofline share."""
import pathlib
import types

import pytest

from chipbench import run, trace

READER = run.load_reader(run.BENCH_DIR, "awac_sweep_roofline")


def test_sweep_bytes():
    # 16 B per edge (row, col, weight, one completion probe) and 32 B per
    # vertex (four state arrays read, four winner arrays written)
    assert READER.sweep_bytes(1, 0) == 16
    assert READER.sweep_bytes(0, 1) == 32
    assert READER.sweep_bytes(1_048_447, 65_536) == 16 * 1_048_447 + 32 * 65_536


def _ctx(awac_ns, rounds, steps=1):
    spans = []
    for k in range(steps):
        spans.append((k * 10e9, k * 10e9 + 1e9, "host-in"))
        spans.append((k * 10e9 + 1e9, k * 10e9 + 9e9, "solve"))
    dev = trace.Device(0, [(1e9, 1e9 + awac_ns, "while.1")],
                       [(1e9, 1e9 + awac_ns, "jit__awac_loop")])
    solves = [types.SimpleNamespace(awac_rounds=rounds)] * steps
    return types.SimpleNamespace(
        trace=trace.Trace({0: dev}, spans), solves=solves, nnz=1_000_000,
        n=50_000, peaks={"hbm_bytes_per_s": 800e9},
        modules={"awac": ["jit__awac_loop"]})


def test_roofline_share():
    # 17.6 MB per round at 800 GB/s is 22 us; two rounds in 44 ms: 0.1 %
    ctx = _ctx(awac_ns=44e6, rounds=2)
    assert READER.read(ctx) == pytest.approx(100 * 22e-6 / 22e-3)


def test_roofline_reads_nothing_without_the_module():
    ctx = _ctx(awac_ns=44e6, rounds=2)
    ctx.modules = {"awac": ["jit_renamed"]}
    assert READER.read(ctx) is None
    assert pathlib.Path(READER.__file__).name == "awac_sweep_roofline.py"
