"""`BENCHMARK.json` against the data files the harness reads: what the
manifest says of a cell has to be what a run of the cell will do."""

import json
import os

import pytest

from benchmark import configs, traffic

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
    assert raw["control"]["serve_args"], "no lower-precision path named"
    mix = traffic.load_traffic(cell["traffic"])
    with open(os.path.join(HERE, "warm", f"{cell['name']}.json")) as f:
        warm = json.load(f)
    assert warm["keys"]
    limits = {**mix["checks"], **warm.get("checks", {})}
    for key in ("reference_rows", "reference_pad_to", "reference_gap_max",
                "reference_gap_mean_max", "full_length_share_min"):
        assert key in limits
    for name in mix["per_layer"]:
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
    assert configs.kv_bytes_per_token(configs.load_config(config)) == want
