"""`BENCHMARK.json` against the data files the harness reads: what the
manifest says of a cell has to be what a run of the cell will do."""

import json
import os

import pytest

from benchmark import configs, run, traffic

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
HERE = os.path.join(ROOT, "benchmark")

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)
CELLS = MANIFEST["workloads"]


def reporting(metric: str) -> list:
    """The cells whose traffic file does not leave `metric` out."""
    return [c["name"] for c in CELLS if metric not in
            traffic.load_traffic(c["traffic"]).get("end_to_end_omit", {})]


def per_layer_of(cell: dict) -> list:
    """What a cell reports with `--trace 1`: its mix's list, then its
    configuration's own."""
    return run.per_layer_names(traffic.load_traffic(cell["traffic"]),
                               configs.load_config(cell["config"]))


@pytest.mark.parametrize("metric", MANIFEST["per_layer"],
                         ids=lambda m: m["name"])
def test_a_per_layer_metrics_cells_follow_from_the_data_files(metric):
    """A cell reports the per-layer metrics its traffic file and its
    configuration's file list; the manifest's `workloads` list is that,
    written out, and without one the metric is for every cell that reports
    the end-to-end metric it moves."""
    want = [c["name"] for c in CELLS if metric["name"] in per_layer_of(c)]
    assert want, "a metric no cell reports"
    assert metric.get("workloads", reporting(metric["moves"])) == want


@pytest.mark.parametrize("metric", MANIFEST["end_to_end"],
                         ids=lambda m: m["name"])
def test_an_end_to_end_metrics_cells_follow_from_the_traffic_files(metric):
    """run.py reports every end-to-end metric but those a cell's traffic
    file leaves out; the manifest's `workloads` list is that, written out."""
    want = reporting(metric["name"])
    assert want, "a metric no cell reports"
    assert metric.get("workloads", [c["name"] for c in CELLS]) == want


@pytest.mark.parametrize("cell", CELLS, ids=lambda c: c["name"])
def test_a_cell_has_its_files_and_its_limits(cell):
    raw = configs.load_config(cell["config"])
    assert raw["chips"] == cell["chips"]
    # a list of `cli serve` flags; empty where the program has no
    # lower-precision path and the lowered reference is the control
    assert isinstance(raw["control"]["serve_args"], list)
    assert hasattr(configs.family(raw), "Reference")
    mix = traffic.load_traffic(cell["traffic"])
    with open(os.path.join(HERE, "warm", f"{cell['name']}.json")) as f:
        warm = json.load(f)
    assert warm["keys"]
    limits = {**mix["checks"], **warm.get("checks", {})}
    for key in ("reference_rows", "reference_pad_to", "reference_gap_max",
                "reference_gap_mean_max", "full_length_share_min"):
        assert key in limits
    names = per_layer_of(cell)
    assert len(names) == len(set(names))
    for name in names:
        with open(os.path.join(HERE, "metrics", f"{name}.json")) as f:
            m = json.load(f)
        assert os.path.exists(os.path.join(HERE, "readers",
                                           f"{m['reader']}.py"))
        listed = [p for p in MANIFEST["per_layer"] if p["name"] == name]
        assert len(listed) == 1 and listed[0]["moves"] == m["moves"]
        # the end-to-end metric it moves is one this cell reports
        assert cell["name"] in reporting(m["moves"])


@pytest.mark.parametrize("config,want", [("mistral-7b-l16", 64 * 1024),
                                         ("qwen2.5-3b", 36 * 1024)])
def test_a_resident_tokens_bytes_at_the_stated_type(config, want):
    """What `correct` holds the engine's own account to (ISSUE 23 gives
    the same two numbers: 64 KiB and 36 KiB of KV a token)."""
    raw = configs.load_config(config)
    assert configs.family(raw).stated_precision(raw) == {
        "kv_bytes_per_token": want}
