"""The staged host-operation metrics (PR 37): `readers/gap_by_op.py`,
`readers/span_mean_ms.py`, `readers/tick_arg_ratio.py`, their metric files,
the list `host_op_metrics.json` and the builder's runner — on a trace made
by hand in which every boundary lies on a whole microsecond (so a second,
naive way of splitting it, one microsecond at a time, must agree to the
ns), and on a slice recorded on the v5e (`recorded_v5e_ops.json.gz`: two
ticks, 0.55 s, of `lfm2-24b-a2b-l9.agent-turns`, cut by
`describe_spans.short_slice` from my own chip run, PR 37)."""

import gzip
import importlib
import json
import os
import re

import pytest

from benchmark import spans, with_host_ops
from benchmark.readers import gap_by_op

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
US = 1000
PHASES = ("admit", "prepare", "pack", "dispatch_prefill", "wait_prefill",
          "dispatch_decode", "wait_decode", "commit", "retire", "idle")
CLASSES = ("engine.gap_session_ms_per_tick",
           "batcher.gap_schedule_ms_per_tick",
           "device.gap_transfer_ms_per_tick",
           "batcher.gap_observe_ms_per_tick")


def metric(name: str) -> dict:
    with open(os.path.join(BENCH, "metrics", f"{name}.json")) as f:
        return json.load(f)


def reader(m: dict):
    return importlib.import_module(f"benchmark.readers.{m['reader']}")


def staged() -> list:
    with open(os.path.join(BENCH, "host_op_metrics.json")) as f:
        return json.load(f)["metrics"]


# one tick of the worker, in us from the tick's start: (phase, start, end)
TICK_PHASES = [("admit", 0, 10), ("prepare", 10, 200), ("pack", 200, 260),
               ("dispatch_prefill", 260, 300), ("wait_prefill", 300, 600),
               ("dispatch_decode", 600, 620), ("wait_decode", 620, 900),
               ("commit", 900, 960), ("retire", 960, 1000)]
# ... and its operations; `prefix_match` lies inside `session_lookup` and
# `prefix_insert` inside `session_put`
TICK_OPS = [("splice", 10, 40), ("session_lookup", 50, 150),
            ("prefix_match", 80, 120), ("rng", 160, 190),
            ("layout", 200, 240), ("tiles", 245, 255),
            ("h2d", 262, 280), ("enqueue", 282, 298),
            ("device", 300, 598), ("h2d", 601, 608), ("enqueue", 609, 619),
            ("device", 621, 880), ("fetch", 880, 890), ("account", 890, 899),
            ("session_put", 900, 950), ("prefix_insert", 920, 940),
            ("account", 951, 958), ("retire_rows", 961, 990)]
TICK_STARTS = (0, 1005)           # 5 us between the two ticks: no span
# the device, on the host's clock: a tick's chunk program 290-540 and its
# decode program 615-870 — so ONE gap, 540-615, spans `device`, the self
# time of `wait_prefill` and `dispatch_decode`, `h2d` and `enqueue`; the
# other runs from a tick's decode program to the next tick's chunk program
PROGRAMS = [(290, 540), (615, 870)]
EARLY = 100                       # the device's clock is 100 us early


def hand_made(with_ops: bool = True) -> dict:
    worker, runtime, mods, ops = [], [], [], []
    for k, t0 in enumerate(TICK_STARTS):
        worker.append(("qtpu.tick", t0 * US, 1000 * US,
                       {"model": "m", "rows": "2", "attn_kv_reads": "400",
                        "attn_kv_streamed": str(100 * (k + 1))}))
        worker += [("qtpu.tick." + p, (t0 + s) * US, (e - s) * US, {})
                   for p, s, e in TICK_PHASES]
        if with_ops:
            worker += [("qtpu.op." + o, (t0 + s) * US, (e - s) * US, {})
                       for o, s, e in TICK_OPS]
        for j, (s, e) in enumerate(PROGRAMS):
            run_id = str(2 * k + j + 1)
            runtime.append(("DoEnqueueProgram", (t0 + s) * US, 3 * US,
                            {"run_id": run_id}))
            mods.append((f"jit_step({run_id})", (t0 + s - EARLY) * US,
                         (e - s) * US, {"run_id": run_id}))
            ops.append(("%fusion.1", (t0 + s - EARLY) * US, (e - s) * US,
                        "jit(step)/layers/while/body/mlp/dot:"))
    # an empty iteration of the worker after them: a tick with no rows
    worker.append(("qtpu.tick", 2100 * US, 50 * US,
                   {"model": "m", "rows": 0.0}))    # as a trace reads a 0
    worker.append(("qtpu.tick.idle", 2100 * US, 50 * US, {}))
    client = [("qtpu.session_drop", 100 * US, 300 * US,
               {"model": "m", "lock_wait_us": "280", "held_us": "20"}),
              ("qtpu.session_drop", 1200 * US, 100 * US,
               {"model": "m", "lock_wait_us": "60", "held_us": "40"})]
    return {"host": {"7": sorted(worker, key=lambda e: (e[1], -e[2])),
                     "8": runtime, "9": client},
            "device": {0: {"modules": mods, "ops": ops}}}


def microsecond_by_microsecond() -> dict:
    """The same split, the slow way: paint each us of the host's line with
    the phase that covers it, then with each operation in order of its
    start (an inner one over its outer one), and count the us of the three
    gaps under each name."""
    paint = [None] * 2200
    for t0 in TICK_STARTS:
        for p, s, e in TICK_PHASES:
            paint[t0 + s:t0 + e] = [("phase", p)] * (e - s)
        for o, s, e in sorted(TICK_OPS, key=lambda x: x[1]):
            paint[t0 + s:t0 + e] = [("op", o)] * (e - s)
    out: dict = {}
    gaps = [(540, 615), (870, 1005 + 290), (1005 + 540, 1005 + 615)]
    for a, b in gaps:
        for us in range(a, b):
            out[paint[us]] = out.get(paint[us], 0) + US
    return out


def test_a_gap_splits_by_exact_overlap_and_the_parts_sum_to_it():
    got = gap_by_op.split(hand_made())
    want = microsecond_by_microsecond()
    assert got["idle_ns"] == (75 + 425 + 75) * US == sum(want.values())
    assert got["by_op"] == {key[1]: ns for key, ns in want.items()
                            if key and key[0] == "op"}
    assert got["by_phase_self"] == {key[1]: ns for key, ns in want.items()
                                    if key and key[0] == "phase"}
    assert got["unnamed_ns"] == want[None] == 5 * US
    assert (sum(got["by_op"].values()) + sum(got["by_phase_self"].values())
            + got["unnamed_ns"]) == got["idle_ns"]
    # the one gap that spans three operations and two phases' self time
    # (540-615): device 58, wait_prefill 2, dispatch_decode 1 + 1, h2d 7,
    # enqueue 6 — twice, once a tick
    assert got["by_op"]["h2d"] == (2 * 7 + 18) * US
    assert got["by_phase_self"]["wait_prefill"] == 2 * 2 * US
    # an operation inside another is taken out of it
    assert got["by_op"]["session_lookup"] == 60 * US
    assert got["by_op"]["prefix_match"] == 40 * US
    assert got["ticks"] == 2                   # the empty iteration is not
    assert got["offset_ns"] == EARLY * US
    assert sorted(got["ops"]["device"]) == [259 * US] * 2 + [298 * US] * 2
    assert got["ops"]["session_put"] == [30 * US] * 2     # self time


def test_innermost_pieces_are_disjoint_and_keep_every_ns():
    pieces, selfs = gap_by_op.innermost(
        [("a", 0, 100), ("b", 10, 40), ("c", 20, 30), ("b", 50, 60),
         ("d", 100, 120)])
    assert pieces == [("a", 0, 10), ("b", 10, 20), ("c", 20, 30),
                      ("b", 30, 40), ("a", 40, 50), ("b", 50, 60),
                      ("a", 60, 100), ("d", 100, 120)]
    assert selfs == {"a": [60], "b": [20, 10], "c": [10], "d": [20]}


def test_the_class_metrics_self_time_and_unnamed_are_the_idle_gap(
        monkeypatch, capsys):
    trace = hand_made()               # one trace a process, as a run has
    monkeypatch.setattr(spans, "trace_of_this_process", lambda: trace)

    def value(name):
        m = metric(name)
        return reader(m).read({}, m)

    total = value("batcher.idle_gap_ms_per_tick")
    assert total == pytest.approx(575e-3 / 2)
    got = gap_by_op.split(hand_made())
    per_tick = 1e6 * got["ticks"]
    device = got["by_op"]["device"] / per_tick
    in_schedule = set(metric("batcher.gap_schedule_ms_per_tick")[
        "phases_self"])
    other_self = sum(ns for p, ns in got["by_phase_self"].items()
                     if p not in in_schedule) / per_tick
    classes = sum(value(name) for name in CLASSES)
    assert classes + device + other_self + got["unnamed_ns"] / per_tick \
        == pytest.approx(total, rel=1e-12)
    named = value("device.idle_named_op_share_pct")
    assert named == pytest.approx(
        100.0 * sum(got["by_op"].values()) / got["idle_ns"])
    assert 0 < named < 100
    # one line for the six metrics, the operations by the idle time under
    # them
    said = [ln for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("[gaps-by-op] ")]
    assert len(said) == 1
    line = json.loads(said[0][len("[gaps-by-op] "):])
    assert line["ticks_with_rows"] == 2
    assert line["idle_s"] == pytest.approx(575e-6)
    assert line["unnamed_s"] == pytest.approx(5e-6)
    assert list(line["by_op"])[0] == "device"
    assert line["by_op"]["rng"] == {
        "calls": 2, "median_us": 30.0, "busy_s": pytest.approx(60e-6),
        "idle_s": pytest.approx(30e-6)}
    assert set(line["idle_s_by_phase_self"]) <= set(PHASES)
    # ... of the idle time that is the worker's: a gap under the empty
    # loop's wait (`idle`, the metric file's `not_host`) waits for callers
    waiting = hand_made()
    waiting["device"][0]["ops"].append(("%fusion.1", 2300 * US, 10 * US, ""))
    got = gap_by_op.split(waiting)
    assert got["by_phase_self"]["idle"] == 50 * US
    monkeypatch.setattr(spans, "trace_of_this_process", lambda: waiting)
    assert value("device.idle_named_op_share_pct") == pytest.approx(
        100.0 * sum(got["by_op"].values()) / (got["idle_ns"] - 50 * US))
    assert value("batcher.idle_gap_ms_per_tick") == pytest.approx(
        got["idle_ns"] / 2e6)


def test_the_class_metrics_take_each_operation_once():
    from quoracle_tpu.infra.telemetry import TICK_OPS as PROGRAM_OPS
    from quoracle_tpu.infra.telemetry import TICK_PHASES as PROGRAM_PHASES
    listed = [o for name in CLASSES for o in metric(name)["ops"]]
    assert sorted(listed + ["device"]) == sorted(PROGRAM_OPS)
    assert tuple(PROGRAM_PHASES) == PHASES
    for name in CLASSES:
        assert set(metric(name).get("phases_self", [])) <= set(PHASES)


def test_a_program_without_operations_reads_the_idle_gap_alone(monkeypatch):
    bare = hand_made(with_ops=False)
    monkeypatch.setattr(spans, "trace_of_this_process", lambda: bare)
    m = metric("batcher.idle_gap_ms_per_tick")
    assert reader(m).read({}, m) == pytest.approx(575e-3 / 2)
    for name in CLASSES + ("device.idle_named_op_share_pct",):
        m = metric(name)
        assert reader(m).read({}, m) is None
    # no trace, no device, no tick: nothing, and nothing raised
    for trace in (None, {"host": bare["host"], "device": {}},
                  {"host": {"8": bare["host"]["8"]},
                   "device": bare["device"]}):
        monkeypatch.setattr(spans, "trace_of_this_process", lambda: trace)
        for name in CLASSES + ("batcher.idle_gap_ms_per_tick",):
            m = metric(name)
            assert reader(m).read({}, m) is None


def test_an_operation_is_not_taken_for_a_phase(monkeypatch):
    """`spans.phases` and `spans.ticks` take `qtpu.tick.<x>` for a phase:
    the operations are `qtpu.op.<x>`, and the accepted readers read the
    same with them in the trace as without."""
    with_ops, without = hand_made(), hand_made(with_ops=False)
    assert {p for p, _, _ in spans.phases(with_ops)} == set(PHASES)
    assert spans.phases(with_ops) == spans.phases(without)
    assert spans.ticks(with_ops) == spans.ticks(without)
    assert spans.idle_by_phase(with_ops) == spans.idle_by_phase(without)
    for name in ("batcher.tick_host_share_pct",
                 "device.idle_attributed_share_pct"):
        m = metric(name)
        monkeypatch.setattr(spans, "trace_of_this_process",
                            lambda: with_ops)
        a = reader(m).read({}, m)
        monkeypatch.setattr(spans, "trace_of_this_process", lambda: without)
        assert reader(m).read({}, m) == a is not None


def test_span_mean_ms_reads_the_drops_of_any_line(monkeypatch, capsys):
    m = metric("engine.session_drop_mean_ms")
    monkeypatch.setattr(spans, "trace_of_this_process", hand_made)
    assert reader(m).read({}, m) == pytest.approx(0.2)
    said = json.loads(capsys.readouterr().out.split("[drops-inside] ")[1])
    assert said["count"] == 2 and said["max_ms"] == pytest.approx(0.3)
    assert said["sum_ms"] == pytest.approx(0.4)
    assert said["mean_lock_wait_us"] == pytest.approx(170.0)
    assert said["whole_run"]["histogram"] == "quoracle_session_drop_wait_ms"
    none = hand_made()
    del none["host"]["9"]
    monkeypatch.setattr(spans, "trace_of_this_process", lambda: none)
    assert reader(m).read({}, m) is None
    monkeypatch.setattr(spans, "trace_of_this_process", lambda: None)
    assert reader(m).read({}, m) is None


def test_tick_arg_ratio_sums_over_the_ticks_that_carry_both(monkeypatch):
    m = metric("kernel.attn_kv_fetch_ratio")
    monkeypatch.setattr(spans, "trace_of_this_process", hand_made)
    assert reader(m).read({}, m) == pytest.approx(300 / 800)
    bare = hand_made()
    bare["host"]["7"] = [(n, s, d, {"rows": a.get("rows", "0")})
                         for n, s, d, a in bare["host"]["7"]]
    monkeypatch.setattr(spans, "trace_of_this_process", lambda: bare)
    assert reader(m).read({}, m) is None


@pytest.mark.parametrize("entry", staged(), ids=lambda e: e["name"])
def test_a_staged_metric_is_ready_to_be_admitted(entry):
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        manifest = json.load(f)
    m = metric(entry["name"])
    assert m["name"] == entry["name"]
    assert re.fullmatch(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}", m["name"])
    assert re.fullmatch(r"[A-Za-z0-9_/%.\-]{1,16}", m["unit"])
    assert m["better"] in ("lower", "higher")
    assert m["source"] in ("device_trace", "program_span",
                           "program_counter", "host_clock")
    assert m["layer"] in {p["layer"] for p in manifest["per_layer"]}
    assert m["moves"] in {e["name"] for e in manifest["end_to_end"]}
    assert callable(reader(m).read)
    assert m["name"] not in {p["name"] for p in manifest["per_layer"]}
    # where it is to be listed: files that are there
    assert entry.get("traffic") or entry.get("configs")
    for folder in ("traffic", "configs"):
        for name in entry.get(folder, []):
            assert os.path.exists(os.path.join(BENCH, folder,
                                               f"{name}.json"))


def test_the_runner_appends_the_list_in_a_root_of_its_own(tmp_path):
    mixes = with_host_ops.staged("traffic", "cold-prompts")
    assert "batcher.idle_gap_ms_per_tick" in mixes
    assert "engine.session_drop_mean_ms" in mixes
    assert "engine.session_drop_mean_ms" not in with_host_ops.staged(
        "traffic", "long-shared-prompt")
    assert with_host_ops.staged("configs", "mistral-7b-l16") == []
    with_host_ops.write_with(str(tmp_path), "traffic", "cold-prompts", mixes)
    with open(os.path.join(BENCH, "traffic", "cold-prompts.json")) as f:
        accepted = json.load(f)
    with open(tmp_path / "traffic" / "cold-prompts.json") as f:
        written = json.load(f)
    assert written["per_layer"] == accepted["per_layer"] + mixes
    assert {k: v for k, v in written.items() if k != "per_layer"} \
        == {k: v for k, v in accepted.items() if k != "per_layer"}
    # nothing it lists is reported by an accepted cell yet
    assert not set(mixes) & set(accepted["per_layer"])


@pytest.fixture
def recorded():
    with gzip.open(os.path.join(HERE, "recorded_v5e_ops.json.gz"),
                   "rt") as f:
        raw = json.load(f)
    return {"host": {k: [tuple(e) for e in evs]
                     for k, evs in raw["host"].items()},
            "device": {int(k): {kind: [tuple(e) for e in evs]
                                for kind, evs in dev.items()}
                       for k, dev in raw["device"].items()}}


def test_recorded_v5e_ops(recorded, monkeypatch, capsys):
    """Two ticks of `lfm2-24b-a2b-l9.agent-turns` on the chip (PR 37): the
    idle time splits to the ns, nearly all of it under named operations,
    and the allocation transaction and the small transfers lead."""
    from quoracle_tpu.infra.telemetry import TICK_OPS as PROGRAM_OPS
    got = gap_by_op.split(recorded)
    assert got["ticks"] == 2 and got["idle_ns"] == 77_150_096
    assert (sum(got["by_op"].values()) + sum(got["by_phase_self"].values())
            + got["unnamed_ns"]) == got["idle_ns"]
    assert set(got["ops"]) <= set(PROGRAM_OPS)
    assert "state_adopt" in got["ops"]            # a model with conv state
    assert {p for p, _, _ in spans.phases(recorded)} <= set(PHASES)
    by_idle = sorted(got["by_op"], key=got["by_op"].get, reverse=True)
    assert by_idle[:3] == ["page_alloc", "h2d", "fetch"]
    assert got["by_op"]["page_alloc"] == 24_153_308
    monkeypatch.setattr(spans, "trace_of_this_process", lambda: recorded)
    for name in CLASSES + ("batcher.idle_gap_ms_per_tick",):
        m = metric(name)
        assert reader(m).read({}, m) > 0
    m = metric("device.idle_named_op_share_pct")
    assert 90 < reader(m).read({}, m) < 100
    m = metric("engine.session_drop_mean_ms")
    assert reader(m).read({}, m) == pytest.approx(211.682416)
    said = json.loads(capsys.readouterr().out.split("[drops-inside] ")[1])
    assert said["count"] == 1 and said["mean_lock_wait_us"] == 211572
    m = metric("kernel.attn_kv_fetch_ratio")
    assert 0 < reader(m).read({}, m) < 1          # rows share their prompt
