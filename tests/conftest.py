"""Test environment: force an 8-virtual-device CPU mesh BEFORE jax backends init.

Multi-chip hardware is not available in CI; sharding tests run against
``--xla_force_host_platform_device_count=8`` exactly as the driver's
dryrun_multichip does. Real-TPU paths are exercised by chip_smoke.py, not
tests. XLA_FLAGS is read lazily at backend init; any pre-existing
device-count flag is overridden, not kept.
"""

import os
import re

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                os.environ.get("XLA_FLAGS", ""))
os.environ["XLA_FLAGS"] = (
    _flags.strip() + " --xla_force_host_platform_device_count=8").strip()
# Suite-wide persistent compilation cache (VERDICT r4 item 6): dozens of
# test files build their own GenerateEngine over the same tiny configs,
# and each construction recompiles identical (prefill, decode) HLO — the
# persistent cache dedupes those across files and runs. Under xdist each
# worker keeps a directory of its own beneath the one parent
# (PYTEST_XDIST_WORKER): six workers writing one directory lost a worker
# to a segfault inside jax's cache write. Where
# JAX_COMPILATION_CACHE_DIR does not already place it, it goes to a TEMP
# dir, so the hundreds of tiny-test-model entries stay out of the
# checkout's own cache (utils/compile_cache.py).
import tempfile

_cache = os.path.join(tempfile.gettempdir(),
                      f"quoracle-test-xla-cache-{os.getuid()}")
# unset, or set by the xdist controller's own import of this file (its
# workers inherit the controller's environment)
if (os.environ.get("JAX_COMPILATION_CACHE_DIR") or _cache) == _cache:
    # The parent must be OWNED by us, mode 0700: /tmp's sticky bit stops
    # deletion, not creation — another user could pre-create a
    # predictable path and plant compiled-executable cache entries this
    # process would load. Refuse a foreign dir (in-checkout cache then).
    try:
        os.makedirs(_cache, mode=0o700, exist_ok=True)
        _st = os.stat(_cache)
        if _st.st_uid != os.getuid():
            raise PermissionError(f"{_cache} owned by uid {_st.st_uid}")
        os.chmod(_cache, 0o700)
        _worker = os.environ.get("PYTEST_XDIST_WORKER")
        if _worker:
            _cache = os.path.join(_cache, _worker)
            os.makedirs(_cache, mode=0o700, exist_ok=True)
        os.environ["JAX_COMPILATION_CACHE_DIR"] = _cache
    except OSError:
        pass

import jax  # noqa: E402

from quoracle_tpu.utils.compile_cache import enable_compilation_cache  # noqa: E402

enable_compilation_cache()

import time  # noqa: E402

import pytest  # noqa: E402

# ---------------------------------------------------------------------------
# Runtime lock-order sanitizer (ISSUE 9): ON for the whole suite unless
# explicitly disabled, so every existing concurrency test doubles as a
# race check. Must happen before any quoracle module creates its locks —
# conftest imports before every test module, and named_lock reads the
# sanitizer flag per acquisition (enable() is retroactive anyway).
# ---------------------------------------------------------------------------

from quoracle_tpu.analysis import lockdep  # noqa: E402

if os.environ.get("QUORACLE_LOCKDEP", "").strip().lower() not in (
        "0", "false", "off"):
    lockdep.enable()


@pytest.fixture(params=["ragged", "gather"])
def paged_path(request, monkeypatch):
    """Session-behaviour tests run once per paged path: every engine the
    test builds serves its sessioned ticks through the ragged programs
    (the default on every platform) or, pinned by the engine's one test
    seam, through the gather programs a tick falls back to (a dp/sp mesh,
    a declined store, a swapped boundary page: generate.ragged_fallback),
    which stay reachable on the chip."""
    if request.param == "gather":
        from quoracle_tpu.models.generate import GenerateEngine
        monkeypatch.setattr(GenerateEngine, "_force_gather_decode", True)
    return request.param


@pytest.fixture(autouse=True)
def _lockdep_guard():
    """Fail any test whose execution produced a lock-order inversion.
    Tests that SEED inversions on purpose (tests/test_races.py) drain
    the ledger themselves before returning."""
    lockdep.LOCKDEP.drain()
    yield
    if not lockdep.enabled():
        return
    inversions = lockdep.LOCKDEP.drain()
    assert not inversions, (
        "lock-order inversion(s) observed (analysis/lockdep.py): "
        + "; ".join(
            f"{i['thread']}: acquiring {i['acquiring']!r} while holding "
            f"{i['violates']} at {i['site']}" for i in inversions))


@pytest.fixture(autouse=True)
def _thread_leak_guard():
    """No non-daemon thread created during a test may survive it (ISSUE
    9 satellite): a leaked non-daemon thread keeps the process alive
    after pytest finishes and is a shutdown bug in the component that
    spawned it. Daemon workers (batcher loops, spill writers, watchdog)
    are owned by objects whose close() the tests drive; the guard only
    hunts the ones that would actually wedge an exit."""
    import threading
    before = {t.ident for t in threading.enumerate()}
    yield
    deadline = time.monotonic() + 2.0
    while time.monotonic() < deadline:
        leaked = [t for t in threading.enumerate()
                  if t.ident not in before and not t.daemon
                  and t.is_alive()]
        if not leaked:
            return
        for t in leaked:
            t.join(timeout=max(0.05, deadline - time.monotonic()))
    leaked = [t for t in threading.enumerate()
              if t.ident not in before and not t.daemon and t.is_alive()]
    assert not leaked, (
        "non-daemon thread(s) leaked by this test: "
        + ", ".join(repr(t.name) for t in leaked))


@pytest.fixture(scope="session")
def eight_devices():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs
