"""The readers of the span, scope and row metrics (PR 24): on data made by
hand, on a slice recorded on the v5e (`recorded_v5e_spans.json.gz`, a second
and a half of `mistral-7b-l16.agent-turns` cut by `describe_spans.short_slice`
from my own chip run, PR 24: host spans, the runtime's enqueues, programs and
operations with their `tf_op`), and the loader on a trace recorded here."""

import gzip
import importlib
import json
import os

import pytest

from benchmark import spans
from benchmark import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
US = 1000


def metric(name: str) -> dict:
    with open(os.path.join(os.path.dirname(HERE), "metrics",
                           f"{name}.json")) as f:
        return json.load(f)


def reader(m: dict):
    return importlib.import_module(f"benchmark.readers.{m['reader']}")


@pytest.fixture
def recorded():
    with gzip.open(os.path.join(HERE, "recorded_v5e_spans.json.gz"),
                   "rt") as f:
        raw = json.load(f)
    return {"host": {k: [tuple(e) for e in evs]
                     for k, evs in raw["host"].items()},
            "device": {int(k): {kind: [tuple(e) for e in evs]
                                for kind, evs in dev.items()}
                       for k, dev in raw["device"].items()}}


def hand_made():
    """One tick of the worker, the device 100 us early on its own clock.
    Host (us): admit 0-10, prepare 10-60, dispatch_prefill 60-100,
    wait_prefill 100-400, dispatch_decode 400-420, wait_decode 420-900,
    commit 900-950, retire 950-1000. Device, on the host's clock: prefill
    100-380, decode 440-880 — written 100 us earlier."""
    ph = [("admit", 0, 10), ("prepare", 10, 60),
          ("dispatch_prefill", 60, 100), ("wait_prefill", 100, 400),
          ("dispatch_decode", 400, 420), ("wait_decode", 420, 900),
          ("commit", 900, 950), ("retire", 950, 1000)]
    worker = [("qtpu.tick", 0, 1000 * US,
               {"model": "m", "rows": "2", "program": "raggedx64x8x4x64"})]
    worker += [("qtpu.tick." + p, s * US, (e - s) * US, {})
               for p, s, e in ph]
    runtime = [("DoEnqueueProgram", 95 * US, 3 * US, {"run_id": "1"}),
               ("DoEnqueueProgram", 415 * US, 3 * US, {"run_id": "2"})]
    d = -100                          # the device's clock is 100 us early
    mods = [("jit_step_paged_ragged(1)", (100 + d) * US, 280 * US,
             {"run_id": "1"}),
            ("jit_step_paged_decode_ragged(2)", (440 + d) * US, 440 * US,
             {"run_id": "2"})]
    pre = "jit(step_paged_decode_ragged)/decode_loop/while/body/"
    ops = [("%fusion.1", (100 + d) * US, 280 * US,
            "jit(step_paged_ragged)/layers/while/body/closed_call/mlp/dot:"),
           ("%while.9", (440 + d) * US, 440 * US, ""),
           ("%copy.1", (440 + d) * US, 100 * US,
            "jit(step_paged_decode_ragged)/decode_loop/while:"),
           ("%slice.2", (540 + d) * US, 60 * US, pre + "layers/while:"),
           ("%reshape.3", (600 + d) * US, 40 * US,
            pre + "layers/while/body/closed_call/attn/jit(ragged_attend)"
                  "/kv_layout/reshape:"),
           ("%ragged_attend.5", (640 + d) * US, 50 * US,
            pre + "layers/while/body/closed_call/attn/jit(ragged_attend)"
                  "/ragged_attend/pallas_call:"),
           ("%fusion.4", (690 + d) * US, 110 * US,
            pre + "layers/while/body/closed_call/mlp/dot_general:"),
           ("%fusion.6", (800 + d) * US, 40 * US, pre + "head/dot_general:"),
           ("%sort.7", (840 + d) * US, 20 * US,
            pre + "sample/top_p/jit(sort)/sort:")]
    return {"host": {"7": sorted(worker, key=lambda e: (e[1], -e[2])),
                     "8": runtime},
            "device": {0: {"modules": mods, "ops": ops}}}


def test_scope_of_takes_the_innermost_name_of_the_programs_own():
    pre = "jit(step)/decode_loop/while/body/"
    assert spans.scope_of(pre + "layers/while/body/closed_call/mlp/"
                          "btd,df->btf/dot_general:") == "mlp"
    assert spans.scope_of(pre + "layers/while/body/dynamic_slice:") \
        == "layers"
    assert spans.scope_of(pre + "layers/while/body/closed_call/attn/"
                          "jit(ragged_attend)/kv_layout/reshape:") \
        == "kv_layout"
    assert spans.scope_of(pre + "sample/top_p/jit(sort)/sort:") == "top_p"
    assert spans.scope_of("jit(step)/decode_loop/while:") == "decode_loop"
    assert spans.scope_of("jit(step)/while:") == spans.UNSCOPED
    assert spans.scope_of("") == spans.UNSCOPED


def test_scope_seconds_are_exclusive_and_sum_to_the_programs_busy_time():
    by = spans.scope_seconds(hand_made(), "step_paged_decode_ragged$")
    # the while holds 420 of its 440 us in children
    assert by == {spans.UNSCOPED: pytest.approx(20e-6),
                  "decode_loop": pytest.approx(100e-6),
                  "layers": pytest.approx(60e-6),
                  "kv_layout": pytest.approx(40e-6),
                  "attn": pytest.approx(50e-6),
                  "mlp": pytest.approx(110e-6),
                  "head": pytest.approx(40e-6),
                  "top_p": pytest.approx(20e-6)}
    assert sum(by.values()) == pytest.approx(440e-6)
    assert spans.scope_seconds(hand_made(), "no_such_program$") is None


@pytest.mark.parametrize("name,want", [
    ("step.decode_kv_move_share_pct", 100.0 * 140 / 440),   # not `layers`
    ("step.decode_head_sample_share_pct", 100.0 * 60 / 440),
    ("step.decode_mlp_share_pct", 100.0 * 110 / 440)])
def test_scope_share_readers_one_reader_three_data_files(
        monkeypatch, capsys, name, want):
    m = metric(name)
    monkeypatch.setattr(spans, "trace_of_this_process", hand_made)
    assert reader(m).read({}, m) == pytest.approx(want)
    assert capsys.readouterr().out.startswith(f"[scopes] {name} ")
    # a program that names no scope (the parent commit): nothing to read
    bare = hand_made()
    bare["device"][0]["ops"] = [(n, s, d, "") for n, s, d, _ in
                                bare["device"][0]["ops"]]
    monkeypatch.setattr(spans, "trace_of_this_process", lambda: bare)
    assert reader(m).read({}, m) is None
    monkeypatch.setattr(spans, "trace_of_this_process", lambda: None)
    assert reader(m).read({}, m) is None


def test_ticks_phases_and_the_host_share(monkeypatch):
    t = spans.ticks(hand_made())
    assert len(t) == 1 and t[0]["args"]["rows"] == "2"
    assert sum(t[0]["phases"].values()) == 1000 * US
    m = metric("batcher.tick_host_share_pct")
    monkeypatch.setattr(spans, "trace_of_this_process", hand_made)
    # all but wait_prefill (300) and wait_decode (480) of 1000 us
    assert reader(m).read({}, m) == pytest.approx(22.0)
    none = {"host": {"8": hand_made()["host"]["8"]}, "device": {}}
    monkeypatch.setattr(spans, "trace_of_this_process", lambda: none)
    assert reader(m).read({}, m) is None


def test_device_clock_offset_from_the_runtimes_enqueues():
    # least shift that puts each program at or after its enqueue: the
    # prefill is written at 0, enqueued at 95
    assert spans.device_offset_ns(hand_made()) == 95 * US
    assert spans.device_offset_ns({"host": {}, "device": {}}) is None


def test_idle_gaps_go_to_the_phase_that_covers_most_of_them(monkeypatch,
                                                            capsys):
    # one gap of 60 us between prefill and decode: on the device's clock
    # 280-340, on the host's (offset 95) 375-435 — 25 us of it under
    # wait_prefill, 20 under dispatch_decode, 15 under wait_decode
    idle = spans.idle_by_phase(hand_made())
    assert idle["offset_ns"] == 95 * US
    assert idle["by_phase"] == {"wait_prefill": pytest.approx(60e-6)}
    assert idle["unattributed_s"] == 0.0 and idle["short_gaps_s"] == 0.0
    m = metric("device.idle_attributed_share_pct")
    monkeypatch.setattr(spans, "trace_of_this_process", hand_made)
    assert reader(m).read({}, m) == pytest.approx(100.0)
    line = capsys.readouterr().out.strip()
    assert line.startswith("[gaps] ")
    said = json.loads(line[len("[gaps] "):])
    assert said["idle_s_by_phase"] == {
        "wait_prefill (runtime latency)": pytest.approx(60e-6)}
    assert said["idle_s"] == pytest.approx(60e-6)
    # with no phase over the gap it is unattributed
    bare = hand_made()
    bare["host"]["7"] = [e for e in bare["host"]["7"]
                         if e[0] == "qtpu.tick.admit"]
    idle = spans.idle_by_phase(bare)
    assert idle["by_phase"] == {} and idle["unattributed_s"] == \
        pytest.approx(60e-6)


def test_row_stamp_readers_join_the_ring_to_the_windows_turns(monkeypatch,
                                                              capsys):
    from quoracle_tpu.infra import introspect
    ms = 1_000_000

    def ring_row(sid, submit, admit, first, done):
        return {"model": "m", "session": sid, "t_submit_ns": submit * ms,
                "t_admit_ns": admit * ms, "t_first_token_ns": first * ms,
                "t_done_ns": done * ms}
    ring = [ring_row("a", 1001, 1011, 1101, 1501),
            ring_row("a", 2001, 2201, 2401, 2901),     # the second turn
            ring_row("b", 1002, 1042, 1302, 1802),
            ring_row("c", 1003, 1004, 1005, 1006)]     # no such turn
    monkeypatch.setattr(introspect, "row_ring", lambda: ring)
    turns = [{"sid": "a", "t_submit": 1.0, "t_done": 1.502,
              "latency_ms": 502.0},
             {"sid": "b", "t_submit": 1.0, "t_done": 1.803,
              "latency_ms": 803.0},
             {"sid": "a", "t_submit": 2.0, "t_done": 2.902,
              "latency_ms": 902.0}]
    ctx = {"ok": turns, "rows": turns}
    assert [r["session"] for r in spans.window_rows(ctx)] == ["a", "b", "a"]
    m = metric("batcher.ttft_p50_ms")
    assert reader(m).read(ctx, m) == pytest.approx(300.0)   # 100, 300, 400
    m = metric("batcher.admit_wait_max_ms")
    assert reader(m).read(ctx, m) == pytest.approx(200.0)   # 10, 40, 200
    said = [json.loads(ln[len("[rows] "):]) for ln in
            capsys.readouterr().out.strip().splitlines()]
    assert [s["rows"] for s in said] == [3, 3]
    assert said[0]["ring_queue_share_pct"] == pytest.approx(
        100.0 * 250 / 2207)
    monkeypatch.setattr(introspect, "row_ring", lambda: [])
    assert reader(m).read(ctx, m) is None


def test_recorded_v5e_spans(recorded):
    ticks = spans.ticks(recorded)
    assert ticks and all(set(t["phases"]) <= {
        "admit", "prepare", "pack", "dispatch_prefill", "wait_prefill",
        "dispatch_decode", "wait_decode", "commit", "retire", "idle"}
        for t in ticks)
    whole = ticks[0]
    for arg in ("model", "rows", "admitted", "real_tokens", "padded_tokens",
                "decode_steps", "program"):
        assert arg in whole["args"], arg
    # the phases tile the tick: what is not under one is the few
    # microseconds between two TraceMe events
    wall = whole["end"] - whole["start"]
    assert 0.999 * wall <= sum(whole["phases"].values()) <= wall
    # the device's clock ran about a millisecond early
    assert 0.2e6 < spans.device_offset_ns(recorded) < 5e6
    by = spans.scope_seconds(recorded, "step_paged_decode_ragged$")
    total = sum(by.values())
    assert by[spans.UNSCOPED] / total < 0.05
    move = sum(by.get(s, 0.0) for s in
               metric("step.decode_kv_move_share_pct")["scopes"])
    # the reading of PR 24, whose scan still sliced the pools: 0.69 with
    # `layers` (0.32), which the metric has not counted since PR 26
    assert 0.3 < move / total < 0.45
    assert 0.5 < (move + by["layers"]) / total < 0.8
    assert 0.15 < by["mlp"] / total < 0.3
    idle = spans.idle_by_phase(recorded)
    named = sum(idle["by_phase"].values())
    assert named / (named + idle["unattributed_s"]) > 0.9


def test_loader_reads_a_trace_recorded_here(tmp_path):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        with jax.named_scope("mlp"):
            return (x @ x).sum()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("qtpu.tick", model="m", rows=2):
        with jax.profiler.TraceAnnotation("qtpu.tick.wait_decode"):
            f(jnp.ones((64, 64))).block_until_ready()
    jax.profiler.stop_trace()
    trace = spans.load(tr.find_xplane(str(tmp_path)))
    assert trace["device"] == {}                   # a CPU has no TPU plane
    (tick,) = spans.ticks(trace)
    assert tick["args"] == {"model": "m", "rows": 2}
    assert set(tick["phases"]) == {"wait_decode"}
    assert spans.idle_by_phase(trace) is None
    assert spans.scope_seconds(trace, "f$") is None
    from benchmark.describe_spans import describe, short_slice
    said = describe(trace)
    assert said["qtpu_names"]["qtpu.tick"]["count"] == 1
    assert any(line["holds_qtpu_tick"]
               for line in said["host_lines"].values())
    assert json.dumps(short_slice(trace, 1.0))


SPAN_METRICS = [
    "step.decode_kv_move_share_pct", "step.decode_head_sample_share_pct",
    "step.decode_mlp_share_pct", "batcher.tick_host_share_pct",
    "device.idle_attributed_share_pct", "batcher.ttft_p50_ms",
    "batcher.admit_wait_max_ms"]


@pytest.mark.parametrize("name", SPAN_METRICS)
def test_a_span_metric_is_whole_and_named_by_both_traffic_files(name):
    """Each of the seven metrics of PR 24 has its file and its reader, moves
    an end-to-end metric of the manifest, and is reported by the cells:
    both traffic files and `BENCHMARK.json` name it (PR 26; until then a
    second runner, `with_spans.py`, appended them in memory)."""
    root = os.path.dirname(HERE)
    with open(os.path.join(os.path.dirname(root), "BENCHMARK.json")) as f:
        manifest = json.load(f)
    e2e = {m["name"] for m in manifest["end_to_end"]}
    m = metric(name)
    assert m["name"] == name and m["moves"] in e2e
    assert m["better"] in ("lower", "higher")
    assert os.path.exists(os.path.join(root, "readers",
                                       f"{m['reader']}.py"))
    for scope in m.get("scopes", []):
        assert scope in spans.scope_names()
    (entry,) = [p for p in manifest["per_layer"] if p["name"] == name]
    assert {k: entry[k] for k in ("unit", "better", "layer", "source",
                                  "moves")} == \
        {k: m[k] for k in ("unit", "better", "layer", "source", "moves")}
    for mix in ("agent-turns", "cold-prompts"):
        with open(os.path.join(root, "traffic", f"{mix}.json")) as f:
            assert json.load(f)["per_layer"].count(name) == 1


def test_the_second_runner_is_gone():
    root = os.path.dirname(HERE)
    assert not os.path.exists(os.path.join(root, "with_spans.py"))
    assert not os.path.exists(os.path.join(root, "span_metrics.json"))
