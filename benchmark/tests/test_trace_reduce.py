"""The reduction from trace events to numbers: on events made by hand, on a
small slice recorded on the v5e (`recorded_v5e_slice.json.gz`, a quarter of
a second of `mistral-7b-l16.agent-turns`, PR 23), and the loader on a trace
recorded here."""

import gzip
import json
import os

import pytest

from benchmark import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
DEV, MODS, OPS = "/device:TPU:0", tr.MODULE_LINE, tr.OPS_LINE
US = 1000


def hand_made():
    """Two programs; the second holds a `while` with two nested ops.
    Times in microseconds."""
    ev = [
        (DEV, MODS, "jit_prefill(1)", 0, 100),
        (DEV, OPS, "%fusion.1", 0, 40),
        (DEV, OPS, "%attend.2", 50, 30),          # 10 idle before it
        (DEV, MODS, "jit_decode(2)", 300, 200),   # 200 idle between programs
        (DEV, OPS, "%while.3", 300, 200),
        (DEV, OPS, "%attend.2", 310, 50),
        (DEV, OPS, "%fusion.4", 400, 60),
        ("/host:CPU", "python3", "$wait", 0, 10_000),
    ]
    return [(p, l, n, s * US, d * US) for p, l, n, s, d in ev]


def test_busy_is_a_union_and_names_are_exclusive_of_children():
    r = tr.reduce(hand_made())
    assert r["window_s"] == pytest.approx(500e-6)
    # 40 + 30 + the whole while (200): nested ops add nothing to busy
    assert r["busy_s"] == pytest.approx(270e-6)
    assert r["ops"]["%while.3"] == pytest.approx(90e-6)     # 200 - 50 - 60
    assert r["ops"]["%attend.2"] == pytest.approx(80e-6)    # both programs
    assert sum(r["ops"].values()) == pytest.approx(r["busy_s"])


def test_programs_their_busy_time_and_the_ops_inside_them():
    r = tr.reduce(hand_made())
    assert r["modules"]["jit_prefill"] == {
        "count": 1, "s": pytest.approx(100e-6),
        "busy_s": pytest.approx(70e-6)}
    assert r["modules"]["jit_decode"]["busy_s"] == pytest.approx(200e-6)
    assert r["module_ops"]["jit_decode"] == {
        "%while.3": 1, "%attend.2": 1, "%fusion.4": 1}
    assert tr.matching(r["modules"], "decode$").keys() == {"jit_decode"}


def test_gaps_are_labelled_by_what_stood_on_either_side():
    gaps = {g[0]: g for g in tr.reduce(hand_made())["gaps"]}
    between = gaps["jit_prefill -> jit_decode"]
    assert between[1] == pytest.approx(220e-6) and between[2] == 1
    assert gaps["between operations (each under 50 us)"][1] == \
        pytest.approx(10e-6)
    b = tr.breakdown(tr.reduce(hand_made()))
    assert b["device_ops"][0][0] == "%while.3"
    assert b["idle_gaps"][0] == ["jit_prefill -> jit_decode",
                                 pytest.approx(220e-6)]


def test_no_device_plane_reads_as_nothing():
    r = tr.reduce([("/host:CPU", "python3", "$wait", 0, 10)])
    assert r["busy_s"] == 0.0 and r["window_s"] == 0.0 and not r["ops"]


def test_short_name_and_module_name():
    assert tr.short_name("%fusion.3 = bf16[8]{0} fusion(%p)") == "%fusion.3"
    assert tr.module_name("jit_step_paged_ragged(123)") == \
        "jit_step_paged_ragged"


def test_recorded_v5e_slice():
    path = os.path.join(HERE, "recorded_v5e_slice.json.gz")
    with gzip.open(path, "rt") as f:
        events = [tuple(e) for e in json.load(f)]
    r = tr.reduce(events)
    assert 0 < r["busy_s"] <= r["window_s"] <= 0.26
    assert sum(r["ops"].values()) == pytest.approx(r["busy_s"], rel=1e-6)
    # a decode step runs the attention kernel once per layer (16 here)
    decode = tr.matching(r["module_ops"], "step_paged_decode_ragged$")
    kernel = sum(sum(tr.matching(c, "^%ragged_attend").values())
                 for c in decode.values())
    assert kernel > 0
    steps = kernel / 16
    step_ms = 1000 * sum(m["busy_s"] for m in tr.matching(
        r["modules"], "step_paged_decode_ragged$").values()) / steps
    assert 5 < step_ms < 100       # the reading of PR 23 was about 35 ms


def test_loader_reads_a_trace_recorded_here(tmp_path):
    import jax
    import jax.numpy as jnp
    with jax.profiler.trace(str(tmp_path)):
        jnp.ones((64, 64)).sum().block_until_ready()
    events = tr.load(tr.find_xplane(str(tmp_path)))
    assert events and all(len(e) == 5 for e in events)
    assert tr.reduce(events)["devices"] == 0       # a CPU has no TPU plane
    from benchmark.describe_trace import describe, device_slice
    assert describe(events)
    assert device_slice(events, 0.25) == []        # and so no slice of one
