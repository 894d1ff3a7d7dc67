"""Every file a cell names is found by its name, and BENCHMARK.json keeps
to the shape the harness reads."""
import json
import re

import pytest

from chipbench import bench

BENCH = bench.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_cell_files_found_by_name(cell):
    w = bench.workload(BENCH, cell)
    cfg = bench.config(w["config"])
    assert cfg["name"] == w["config"]
    traffic = bench.traffic(w["traffic"])
    assert hasattr(bench.driver(traffic["driver"]), "run")
    limits = bench.limits(cell)
    assert limits and all(v > 0 for v in limits.values())
    e2e = [m["name"] for m in bench.metrics_for(BENCH, "end_to_end", cell)]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert bench.metrics_for(BENCH, "per_layer", cell)


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_metric_reader_found(metric):
    assert callable(bench.metric_reader(metric).read)


def test_metric_falls_back_to_its_base_name():
    assert bench.metric_reader("idle_share.some_new_cell") is \
        bench.metric_reader("idle_share.decode")


def test_kernel_cost_files():
    for path in (bench.BENCH_DIR / "kernels").glob("*.py"):
        if path.stem.startswith(("tile_", "flash")):
            assert callable(bench.kernel_cost(path.stem).cost)
    assert bench.kernel_cost("no_such_kernel") is None


def test_unknown_device_kind_is_an_error():
    with pytest.raises(bench.BenchError):
        bench.peaks("cpu")
    assert bench.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12


def test_manifest_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[k]]
    assert all(NAME.match(n) for n in names)
    for k in ("configs", "workloads"):
        ns = [x["name"] for x in BENCH[k]]
        assert len(ns) == len(set(ns))
    metric_names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(metric_names) == len(set(metric_names))
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    layers = set()
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        layers.add(m["layer"])
        for cell in m["workloads"]:
            reported = [x["name"] for x in
                        bench.metrics_for(BENCH, "end_to_end", cell)]
            assert m["moves"] in reported
    assert all(0 < m["bound"] <= 0.25 for m in BENCH["end_to_end"])
    for c in BENCH["configs"]:
        assert c["file"].startswith("chipbench/configs/")
        assert json.loads((bench.ROOT / c["file"]).read_text())["reduced"] \
            == c["reduced"]
    assert len(json.dumps(BENCH)) < 64 * 1024
