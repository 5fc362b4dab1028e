"""The harness finds a configuration, a traffic mix and a metric by name:
adding one takes new files and entries, and no edit of its code."""
import json

from chipbench import run
from chipbench.tests import _tiny

NEW_METRIC = '''
def read(ctx):
    return float(len(ctx.solves))
'''

LATENCY_METRIC = '''
def read(ctx):
    return max((s.latency_s for s in ctx.solves), default=None)
'''


def test_new_files_are_found_by_name(tmp_path):
    root, bench_dir = _tiny.bench_root(tmp_path)
    cfg = json.loads((bench_dir / "configs" / "uniform-d16.json").read_text())
    cfg.update(name="banded-d8", kind="banded", avg_degree=8)
    (bench_dir / "configs" / "banded-d8.json").write_text(json.dumps(cfg))
    mix = json.loads((bench_dir / "traffic" / "refactor-cold.json")
                     .read_text())
    mix.update(chain_length=3, weight_jitter=0.05)
    (bench_dir / "traffic" / "refactor-jumpy.json").write_text(json.dumps(mix))
    (bench_dir / "metrics" / "solves_seen.py").write_text(NEW_METRIC)
    cell = "banded-d8.refactor-jumpy"
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "banded-d8", "source": "test",
                             "file": "chipbench/configs/banded-d8.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": cell, "config": "banded-d8",
                               "traffic": "refactor-jumpy", "chips": 1,
                               "why": "test"})
    bench["end_to_end"].append({"name": "solves_seen", "unit": "solves",
                                "better": "higher", "bound": 0.1,
                                "source": "host_clock", "workloads": [cell]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    assert run.load_data(bench_dir, "configs", "banded-d8")["kind"] == "banded"
    line = _tiny.run_cell(root, bench_dir, cell)
    assert line["correct"] is True, line["checks"]
    assert line["metrics"]["solves_seen"]["value"] == line["attempted"]
    assert set(line["metrics"]) == {"setup_s", "solves_seen"}


def test_new_traffic_with_another_walk_start_and_loop(tmp_path):
    """A warm-started, open-loop traffic on per-seed instances, walked
    0 -> last -> 0, with a latency reader: data and a reader file only."""
    root, bench_dir = _tiny.bench_root(tmp_path)
    mix = json.loads((bench_dir / "traffic" / "refactor-cold.json")
                     .read_text())
    mix.update(instance_seed=None, walk="pingpong", start="warm",
               loop="open", rate_per_s=200.0, chain_length=3)
    (bench_dir / "traffic" / "stream-warm.json").write_text(json.dumps(mix))
    (bench_dir / "metrics" / "latency_max_s.py").write_text(LATENCY_METRIC)
    cell = "uniform-d16.stream-warm"
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": cell, "config": "uniform-d16",
                               "traffic": "stream-warm", "chips": 1,
                               "why": "test"})
    bench["end_to_end"].append({"name": "latency_max_s", "unit": "s",
                                "better": "lower", "bound": 0.1,
                                "source": "host_clock", "workloads": [cell]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    line = _tiny.run_cell(root, bench_dir, cell, seconds=0.5)
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 2
    assert line["metrics"]["latency_max_s"]["value"] > 0


def test_metric_without_workloads_key_applies_to_every_cell():
    bench = {"end_to_end": [{"name": "a"}, {"name": "b", "workloads": ["x"]}]}
    assert [m["name"] for m in run.cell_metrics(bench, "end_to_end", "y")] \
        == ["a"]
    assert [m["name"] for m in run.cell_metrics(bench, "end_to_end", "x")] \
        == ["a", "b"]


def test_every_metric_of_the_benchmark_has_a_reader():
    bench = run.load_benchmark()
    for section in ("end_to_end", "per_layer"):
        for entry in bench[section]:
            assert callable(run.load_reader(run.BENCH_DIR, entry["name"]).read)
    for cell in bench["workloads"]:
        run.load_data(run.BENCH_DIR, "configs", cell["config"])
        run.load_data(run.BENCH_DIR, "traffic", cell["traffic"])
