"""qlint analyzer tests (ISSUE 9): per-rule fixture snippets asserting
exact finding locations, the runtime sanitizer's core semantics, the
CLI's exit-code contract, and the self-run — the analyzers over
quoracle_tpu/ itself must match the committed (empty) baseline, which is
exactly what the CI gate enforces.
"""

import json
import os
import textwrap
import threading
import time

from quoracle_tpu.analysis import common, compilekeys, lockdep, locks
from quoracle_tpu.analysis import registry as registry_pass
from quoracle_tpu.analysis import skips
from quoracle_tpu.tools import qlint


def mod(rel: str, text: str) -> common.SourceModule:
    return common.SourceModule(rel, rel, textwrap.dedent(text))


def by_rule(findings, rule):
    return [f for f in findings if f.rule == rule]


# ---------------------------------------------------------------------------
# locks pass
# ---------------------------------------------------------------------------

def test_lock_cycle_detected_between_plain_locks():
    m = mod("quoracle_tpu/x.py", """\
        import threading

        class B:
            def __init__(self):
                self._lock = threading.Lock()

            def two(self):
                with self._lock:
                    pass

            def three(self, a: "A"):
                with self._lock:
                    a.one()

        class A:
            def __init__(self):
                self._lock = threading.Lock()
                self.b = B()

            def one(self):
                with self._lock:
                    self.b.two()
        """)
    fs = by_rule(locks.run([m]), "lock-cycle")
    assert len(fs) == 1, fs
    assert "A._lock" in fs[0].message and "B._lock" in fs[0].message


def test_lock_hierarchy_violation_exact_site():
    m = mod("quoracle_tpu/x.py", """\
        class S:
            def __init__(self):
                self._m = named_lock("metrics")
                self._s = named_lock("session.store", rlock=True)

            def bad(self):
                with self._m:
                    with self._s:
                        pass

            def good(self):
                with self._s:
                    with self._m:
                        pass
        """)
    fs = by_rule(locks.run([m]), "lock-hierarchy")
    assert len(fs) == 1, fs
    assert fs[0].line == 8
    assert fs[0].symbol == "S.bad"
    assert "session.store" in fs[0].message


def test_blocking_under_bookkeeping_lock_and_coarse_exempt():
    m = mod("quoracle_tpu/x.py", """\
        import time

        class Q:
            def __init__(self):
                self._lock = named_lock("batcher")
                self._serve = named_lock("engine.paged")

            def bad(self):
                with self._lock:
                    time.sleep(1)

            def fine(self):
                with self._serve:
                    time.sleep(1)
        """)
    fs = by_rule(locks.run([m]), "lock-blocking")
    assert len(fs) == 1, fs
    assert fs[0].line == 10 and fs[0].symbol == "Q.bad"


def test_blocking_through_call_edge_is_attributed():
    m = mod("quoracle_tpu/x.py", """\
        import numpy as np

        class D:
            def __init__(self):
                self._lock = named_lock("tier.disk")

            def _write(self, p):
                np.savez(p)

            def save(self, p):
                with self._lock:
                    self._write(p)
        """)
    fs = by_rule(locks.run([m]), "lock-blocking")
    assert len(fs) == 1, fs
    assert fs[0].line == 8          # the np.savez site, not the with
    assert "tier.disk" in fs[0].message


def test_allow_comment_suppresses_lock_blocking():
    m = mod("quoracle_tpu/x.py", """\
        import time

        class Q:
            def __init__(self):
                self._lock = named_lock("batcher")

            def bad(self):
                with self._lock:
                    # qlint: allow[lock-blocking] intentional for the test
                    time.sleep(1)
        """)
    assert by_rule(locks.run([m]), "lock-blocking") == []


def test_try_acquire_is_exempt_from_hierarchy():
    m = mod("quoracle_tpu/x.py", """\
        class S:
            def __init__(self):
                self._m = named_lock("metrics")
                self._s = named_lock("session.store", rlock=True)

            def probe(self):
                with self._m:
                    if self._s.acquire(blocking=False):
                        self._s.release()
        """)
    assert by_rule(locks.run([m]), "lock-hierarchy") == []


# ---------------------------------------------------------------------------
# compilekeys pass
# ---------------------------------------------------------------------------

def test_jit_in_call_path_and_module_level_decorator_ok():
    m = mod("quoracle_tpu/serving/hot.py", """\
        import functools
        import jax

        @functools.partial(jax.jit, static_argnames=("n",))
        def step(x, n=4):
            return x

        def hot_fn(x):
            f = jax.jit(lambda y: y)
            return f(x)
        """)
    fs = by_rule(compilekeys.run([m]), "jit-in-call-path")
    assert len(fs) == 1, fs
    assert fs[0].line == 9 and fs[0].symbol == "hot_fn"


def test_jit_unhashable_static_default():
    m = mod("quoracle_tpu/serving/hot.py", """\
        import functools
        import jax

        @functools.partial(jax.jit, static_argnames=("cfg",))
        def step(x, cfg=[1, 2]):
            return x
        """)
    fs = by_rule(compilekeys.run([m]), "jit-unhashable-static")
    assert len(fs) == 1 and fs[0].line == 5 and fs[0].symbol == "step"


def test_hot_path_sync_item_flagged_but_stats_exempt():
    m = mod("quoracle_tpu/serving/hot.py", """\
        def decode_tick(x):
            return x.item()

        def stats(x):
            return x.item()
        """)
    fs = by_rule(compilekeys.run([m]), "hot-path-sync")
    assert len(fs) == 1 and fs[0].symbol == "decode_tick"


def test_jit_unregistered_class_flagged():
    m = mod("quoracle_tpu/serving/hot.py", """\
        import jax

        class NoLedger:
            def __init__(self):
                self._step = jax.jit(lambda x: x)

        class Ledgered:
            def __init__(self):
                self._step = jax.jit(lambda x: x)
                self.compiles = CompileRegistry("m")

            def dispatch(self, shape):
                self.compiles.record(shape, 0.0)
        """)
    fs = by_rule(compilekeys.run([m]), "jit-unregistered")
    assert [f.symbol for f in fs] == ["NoLedger"]


# ---------------------------------------------------------------------------
# registry pass
# ---------------------------------------------------------------------------

def _registry_fixture(tmp_path):
    (tmp_path / "ARCHITECTURE.md").write_text(
        "docs: quoracle_documented_total and TOPIC_GOOD good:topic and "
        "the good_event flight kind\n")
    tel = mod(registry_pass.TELEMETRY_REL, """\
        GOOD = METRICS.counter("quoracle_documented_total", "h")
        DEAD = METRICS.gauge("quoracle_dead_gauge", "h")
        """)
    bus = mod(registry_pass.BUS_REL, """\
        TOPIC_GOOD = "good:topic"
        """)
    fr = mod(registry_pass.FLIGHTREC_REL, """\
        FLIGHT_EVENTS: dict = {"good_event": "fine"}
        """)
    user = mod("quoracle_tpu/serving/user.py", """\
        from quoracle_tpu.infra.telemetry import GOOD

        TOPIC_MINE = "mine:topic"

        def f(flight):
            GOOD.inc()
            name = "quoracle_documented_total"
            ghost = "quoracle_ghost_total"
            raw = "good:topic"
            flight.record("good_event")
            flight.record("mystery_event")
        """)
    return tmp_path, [tel, bus, fr, user]


def test_registry_unknown_foreign_raw_and_unregistered(tmp_path):
    root, mods = _registry_fixture(tmp_path)
    fs = registry_pass.run(mods, str(root))
    unknown = by_rule(fs, "instrument-unknown")
    assert [f.symbol for f in unknown] == ["quoracle_ghost_total"]
    assert by_rule(fs, "topic-foreign-definition")[0].symbol == \
        "TOPIC_MINE"
    raw = by_rule(fs, "topic-raw-string")
    assert len(raw) == 1 and raw[0].path.endswith("user.py")
    unreg = by_rule(fs, "flight-event-unregistered")
    assert [f.symbol for f in unreg] == ["mystery_event"]
    # documented + referenced name is clean; undocumented dead gauge is
    # both undocumented and unused
    assert [f.symbol for f in by_rule(fs, "instrument-undocumented")] \
        == ["quoracle_dead_gauge"]
    assert [f.symbol for f in by_rule(fs, "instrument-unused")] \
        == ["quoracle_dead_gauge"]
    assert by_rule(fs, "topic-undocumented") == []
    assert by_rule(fs, "flight-event-orphaned") == []


# ---------------------------------------------------------------------------
# skips pass
# ---------------------------------------------------------------------------

def test_skip_markers_detected_through_aliases():
    m = mod("tests/test_fixture.py", """\
        import pytest as pt
        from unittest import skip as s

        @pt.mark.skip
        def test_a():
            pass

        @s("flaky")
        def test_b():
            pass

        def test_c():
            pt.skip("nope")

        torch = pt.importorskip("torch")

        def test_d():
            pass
        """)
    fs = skips.run([m])
    assert [(f.line, f.symbol) for f in fs] == [
        (4, "test_a"), (8, "test_b"), (13, "pytest.skip")]


def test_module_level_pytestmark_detected():
    m = mod("tests/test_fixture.py", """\
        import pytest

        pytestmark = pytest.mark.skipif(True, reason="nope")
        """)
    fs = skips.run([m])
    assert len(fs) == 1 and fs[0].line == 3


# ---------------------------------------------------------------------------
# runtime sanitizer (unit level; the race-level tests live in
# tests/test_races.py)
# ---------------------------------------------------------------------------

def test_named_lock_unknown_name_fails_fast():
    try:
        lockdep.named_lock("not.in.hierarchy")
    except ValueError as e:
        assert "hierarchy" in str(e)
    else:
        raise AssertionError("unknown lock name must raise")


def test_inversion_detected_and_drained():
    was = lockdep.enabled()
    lockdep.enable()
    try:
        lockdep.LOCKDEP.drain()
        inner = lockdep.named_lock("metrics")
        outer = lockdep.named_lock("session.store", rlock=True)
        with outer:
            with inner:
                pass                      # descending: fine
        assert lockdep.LOCKDEP.inversions() == []
        with inner:
            with outer:                   # ascending: inversion
                pass
        inv = lockdep.LOCKDEP.drain()
        assert len(inv) == 1
        assert inv[0]["acquiring"] == "session.store"
        assert ("metrics", 60) in inv[0]["violates"]
        assert lockdep.LOCKDEP.inversions() == []
    finally:
        if not was:
            lockdep.disable()


def test_try_acquire_and_reentrancy_exempt_at_runtime():
    was = lockdep.enabled()
    lockdep.enable()
    try:
        lockdep.LOCKDEP.drain()
        inner = lockdep.named_lock("metrics")
        outer = lockdep.named_lock("session.store", rlock=True)
        with inner:
            assert outer.acquire(blocking=False)
            outer.release()
        with outer:
            with outer:                   # re-entrant RLock
                pass
        assert lockdep.LOCKDEP.drain() == []
    finally:
        if not was:
            lockdep.disable()


def test_disabled_sanitizer_records_nothing():
    was = lockdep.enabled()
    lockdep.disable()
    try:
        lockdep.LOCKDEP.drain()
        inner = lockdep.named_lock("metrics")
        outer = lockdep.named_lock("session.store", rlock=True)
        with inner:
            with outer:
                pass
        assert lockdep.LOCKDEP.drain() == []
    finally:
        if was:
            lockdep.enable()


def test_held_stack_tracks_per_thread():
    was = lockdep.enabled()
    lockdep.enable()
    try:
        lockdep.LOCKDEP.drain()
        a = lockdep.named_lock("session.store", rlock=True)
        seen = {}

        def worker():
            seen["inside"] = lockdep.LOCKDEP.held()

        with a:
            t = threading.Thread(target=worker)
            t.start()
            t.join()
            assert [h[0] for h in lockdep.LOCKDEP.held()] == \
                ["session.store"]
        assert seen["inside"] == []      # other thread holds nothing
        assert lockdep.LOCKDEP.held() == []
        lockdep.LOCKDEP.drain()
    finally:
        if not was:
            lockdep.disable()


# ---------------------------------------------------------------------------
# CLI contract + self-run
# ---------------------------------------------------------------------------

def _mini_repo(tmp_path):
    (tmp_path / "quoracle_tpu").mkdir()
    (tmp_path / "quoracle_tpu" / "__init__.py").write_text("")
    (tmp_path / "tests").mkdir()
    (tmp_path / "tests" / "test_x.py").write_text(
        "import pytest\n\n"
        "@pytest.mark.skip\n"
        "def test_y():\n    pass\n")
    return tmp_path


def test_exit_codes_and_baseline_round_trip(tmp_path, capsys):
    root = str(_mini_repo(tmp_path))
    # 1: a new finding with no baseline
    assert qlint.main(["--root", root]) == 1
    # 0 after accepting it into the baseline
    assert qlint.main(["--root", root, "--update-baseline"]) == 0
    assert qlint.main(["--root", root]) == 0
    # stale entries flip to 1 only under --strict-baseline
    (tmp_path / "tests" / "test_x.py").write_text(
        "def test_y():\n    pass\n")
    assert qlint.main(["--root", root]) == 0
    assert qlint.main(["--root", root, "--strict-baseline"]) == 1
    # 2 on an unknown rule
    assert qlint.main(["--rules", "definitely-not-a-rule"]) == 2
    capsys.readouterr()


def test_json_format_shape(tmp_path, capsys):
    root = str(_mini_repo(tmp_path))
    assert qlint.main(["--root", root, "--format", "json"]) == 1
    payload = json.loads(capsys.readouterr().out)
    assert payload["findings"] and payload["new"]
    f = payload["new"][0]
    assert f["rule"] == "test-skip" and f["path"] == "tests/test_x.py"
    assert set(f) >= {"rule", "path", "line", "symbol", "message",
                      "fingerprint"}


def test_self_run_matches_committed_baseline():
    """The acceptance gate: qlint over THIS repo reports exactly the
    committed baseline (which ships empty — every finding the pass
    surfaced at introduction was fixed or annotated inline), inside the
    30 s wall budget."""
    root = common.repo_root(os.path.dirname(__file__))
    t0 = time.monotonic()
    findings = qlint.run_passes(root)
    wall = time.monotonic() - t0
    baseline = common.load_baseline(
        os.path.join(root, common.BASELINE_NAME))
    new, _ = common.diff_baseline(findings, baseline)
    assert new == [], "\n".join(f.render() for f in new)
    assert wall < 30.0, f"qlint self-run took {wall:.1f}s (budget 30s)"


def test_fingerprint_stable_across_line_drift():
    a = common.Finding("lock-blocking", "p.py", 10, "C.m", "msg")
    b = common.Finding("lock-blocking", "p.py", 99, "C.m", "msg")
    c = common.Finding("lock-blocking", "p.py", 10, "C.m", "other")
    assert a.fingerprint == b.fingerprint != c.fingerprint
