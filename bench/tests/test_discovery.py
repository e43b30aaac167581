"""A configuration, a mix, a metric and a kernel added as new files are
found by name, with no edit to a file that is there, and so is a
configuration of another family with its family module; an unknown
device is refused."""

import json
import shutil

import pytest

import spec
import tiny

FAMILY_NAMES = ("arch_config", "init_leaf", "engine_options", "prompt",
                "inputs", "submit", "lane_key", "read_lane", "Reference",
                "request_flops")


@pytest.fixture
def tree(tmp_path):
    shutil.copytree(spec.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    return tmp_path


def test_new_files_are_found(tree):
    """Whisper's and the test-only dense family's new configurations,
    with a family module, mixes, limits and a metric, all new files and
    ``BENCHMARK.json`` entries, are found by name; the dense cell runs
    ``correct`` through the harness on the CPU; no file of the copied
    tree other than ``BENCHMARK.json`` changes."""
    import run
    bench_dir = tree / "bench"
    before = {p: p.read_bytes() for p in bench_dir.rglob("*")
              if p.is_file()}
    cfg = spec.load_config(spec.load_benchmark(tree), "whisper-tiny.en",
                           root=tree)
    cfg["name"] = "whisper-new"
    (bench_dir / "configs" / "whisper-new.json").write_text(json.dumps(cfg))
    (bench_dir / "mixes" / "bursty.json").write_text(
        json.dumps({"kind": "open", "rate_per_s": 3.0}))
    (bench_dir / "limits" / "new.bursty.json").write_text(
        json.dumps({"requests": 4, "token_gap": 1.0}))
    (bench_dir / "metrics" / "new_metric.py").write_text(
        "def read(run):\n    return 42.0\n")
    shutil.copy(tiny.HERE + "/families/dense.py",
                bench_dir / "families" / "dense.py")
    (bench_dir / "configs" / "dense-test.json").write_text(
        json.dumps(tiny.DENSE))
    (bench_dir / "mixes" / "chat.json").write_text(json.dumps(tiny.CHAT))
    (bench_dir / "limits" / "dense.chat.json").write_text(
        json.dumps(tiny.CHAT_LIMITS))
    bench = spec.load_benchmark(tree)
    bench["configs"].append({"name": "whisper-new", "source": "x",
                             "file": "bench/configs/whisper-new.json",
                             "reduced": [], "why": "x"})
    bench["configs"].append({"name": "dense-test", "source": "x",
                             "file": "bench/configs/dense-test.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "new.bursty", "config":
                               "whisper-new", "traffic": "bursty",
                               "chips": 1, "why": "x"})
    bench["workloads"].append({"name": "dense.chat", "config": "dense-test",
                               "traffic": "chat", "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": "new_metric", "unit": "ms",
                               "better": "lower", "source": "host_clock",
                               "layer": "gateway", "moves": "ttft_p95_s",
                               "workloads": ["new.bursty"]})
    for m in bench["end_to_end"]:
        if m["name"] in ("ttft_p95_s", "e2e_p95_s"):
            m["workloads"].append("dense.chat")
    (tree / "BENCHMARK.json").write_text(json.dumps(bench))

    bench = spec.load_benchmark(tree)
    cell = spec.find_cell(bench, "new.bursty")
    assert spec.load_config(bench, cell["config"], root=tree)["name"] \
        == "whisper-new"
    assert spec.load_mix(cell["traffic"], base=bench_dir)["rate_per_s"] \
        == 3.0
    assert spec.load_limits(cell["name"], base=bench_dir)["requests"] == 4
    names = [m["name"] for m in
             spec.cell_metrics(bench, "new.bursty", "per_layer")]
    assert names == ["new_metric"]
    assert spec.metric_reader("new_metric", base=bench_dir).read(None) \
        == 42.0

    cell = spec.find_cell(bench, "dense.chat")
    cfg = spec.load_config(bench, cell["config"], root=tree)
    family = spec.family_module(cfg["family"], base=bench_dir)
    for name in FAMILY_NAMES:
        assert hasattr(family, name), name
    out = run.run_cell(bench, cell, cfg,
                       spec.load_mix(cell["traffic"], base=bench_dir),
                       spec.load_limits(cell["name"], base=bench_dir),
                       seed=2**32 + 11, seconds=2.0, trace=False,
                       peaks=spec.load_peaks("TPU v5 lite"), family=family,
                       log=lambda m: None)
    assert out["correct"], out["compared"]
    assert set(out["compared"]) == {"token_gap", "short_outputs",
                                    "self_kv_off"}
    assert set(out["metrics"]) == {"ttft_p95_s", "e2e_p95_s", "setup_s"}
    assert out["attempted"] > 0 and out["failed"] == 0
    assert all(p.read_bytes() == b for p, b in before.items())


def test_every_named_piece_exists():
    bench = spec.load_benchmark()
    for cell in bench["workloads"]:
        cfg = spec.load_config(bench, cell["config"])
        family = spec.family_module(cfg["family"])
        for name in FAMILY_NAMES:
            assert hasattr(family, name), (cfg["family"], name)
        spec.load_mix(cell["traffic"])
        spec.load_limits(cell["name"])
        for m in spec.cell_metrics(bench, cell["name"], "per_layer"):
            assert callable(spec.metric_reader(m["name"]).read)
    for m in bench["per_layer"]:
        if m["name"].endswith("_roofline"):
            km = spec.kernel_model(m["name"][:-len("_roofline")])
            assert km.TRACE_NAMES and callable(km.cost)


def test_unknown_device_is_refused():
    assert spec.load_peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        spec.load_peaks("TPU v9 imaginary")
