"""Tiered KV: host offload, session hibernation, and a restart-surviving
prefix store (ISSUE 7 tentpole).

Before this module the KV tier ladder had exactly one rung: a session (or
radix-cache leaf) lived in the HBM page pool until ``SessionStore.alloc``'s
eviction ladder destroyed it, and the next touch paid a full re-prefill.
Agent sessions spend most of their wall-clock WAITING — on actions, on
children, on wait-timers (PAPERS.md "Stateful Inference for Low-Latency
Multi-Agent Tool Calling") — so at any instant most resident pages belong
to nobody who is decoding. Host-memory offload is the standard TPU-serving
answer to that capacity wall (PAPERS.md Gemma-on-TPU serving): HBM holds
the working set, host RAM holds the parked set, disk holds what should
survive the process.

Three tiers, managed by :class:`TierManager` (one per engine/SessionStore):

  HBM   — the device page pool (models/generate.py SessionStore). Unchanged
          semantics; still the only tier attention can read.
  HOST  — :class:`HostPageStore`: numpy copies of demoted sessions and
          stripped prefix-cache leaves, LRU-bounded by ``host_bytes``.
          Eviction from HBM stops being destruction: ``alloc``'s ladder
          DEMOTES here (one ``device_get`` per victim) before releasing
          pages, and a demoted session touched again RESTORES by page-in
          (``device_put`` + the pool scatter the serving path already
          uses) instead of re-prefilling. Refcounts for shared/COW pages
          are untouched: demote copies content and releases only the
          victim's own references, so adopters and the radix tree keep
          reading the still-resident originals (prefix_cache.py I1/I2).
  DISK  — :class:`DiskPrefixStore`: checksummed page-aligned prefix
          blocks under ``disk_dir``. Prefix-cache inserts persist their
          blocks (dedup by content hash), so a RESTARTED process lazily
          warms from its predecessor's prefixes: a radix-tree miss falls
          through to host then disk, pages in, and re-inserts the block.
          Corrupt entries (crc mismatch, torn writes) are skipped and
          unlinked — a bad file must never poison a serving prefix.

Restore invariant (tier-1 tested): a hibernated-and-restored session is
BIT-IDENTICAL to one that never left HBM — device_get/device_put round a
page's bytes exactly, the restored session re-enters the store with the
same tokens/start_pos, and the LCP resume path neither knows nor cares
where the pages spent the interim. Temp-0 outputs therefore match exactly
with tiering on or off.

Locking: demote runs inside ``SessionStore.alloc`` (store lock held, and
the engine's ``_paged_lock`` held by every sessioned caller — the pool
arrays are only ever touched under it). Restore is called from the
engine's session-lookup path (same locks) or from ``prefetch`` (which
try-acquires the engine lock itself, so a busy engine skips the warm-up
rather than blocking the submitter — the generate path restores
synchronously anyway). Disk writes NEVER happen under those locks:
demote/persist only copy device pages host-side (one ``device_get`` per
victim — unavoidable, the pages are about to be recycled) and queue the
npz write to a daemon spill writer; ``flush_spills`` drains it when a
caller needs durability (tests, orderly shutdown).
"""

from __future__ import annotations

import functools
import hashlib
import logging
import os
import queue
import threading
import time
import zlib
from collections import OrderedDict
from typing import Optional, Sequence

import jax
import numpy as np

from quoracle_tpu.analysis.lockdep import named_lock

logger = logging.getLogger(__name__)


def _round_up_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _scatter_pages(k_pool, v_pool, k_host, v_host, pages):
    """Page-in: host block KV → pool pages in place (pools donated, same
    aliasing discipline as generate.py's step_scatter_prompt). ``pages``
    may be padded with 0 — page 0 is scratch by construction, so padded
    writes land harmlessly. The host pages arrive in the pool's stored
    layout, [L, n, page, KV·HD] (``_as_stored``)."""
    k_pool = k_pool.at[:, pages].set(k_host.astype(k_pool.dtype))
    v_pool = v_pool.at[:, pages].set(v_host.astype(v_pool.dtype))
    return k_pool, v_pool


@functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
def _scatter_pages_q(k_pool, v_pool, ks_pool, vs_pool, k_host, v_host,
                     ks_host, vs_host, pages):
    """Quantized page-in (ISSUE 13): int8 payload pages AND their fp32
    scale blocks land together — a restored page is byte-identical to
    the demoted one, scales included."""
    k_pool = k_pool.at[:, pages].set(k_host.astype(k_pool.dtype))
    v_pool = v_pool.at[:, pages].set(v_host.astype(v_pool.dtype))
    ks_pool = ks_pool.at[:, pages].set(ks_host.astype(ks_pool.dtype))
    vs_pool = vs_pool.at[:, pages].set(vs_host.astype(vs_pool.dtype))
    return k_pool, v_pool, ks_pool, vs_pool


def _as_stored(pages_kv: np.ndarray) -> np.ndarray:
    """Host pages in the pool's stored layout, [L, n, page, KV·HD]
    (generate.py ``_ensure_pool``). What ``_gather_host`` reads has it
    already; an entry persisted before the pool was stored lane-flat
    holds the same bytes as [L, n, page, KV, HD], and on the host the
    view is free."""
    return pages_kv.reshape(*pages_kv.shape[:3], -1)


class _HostSession:
    __slots__ = ("tokens", "start_pos", "k", "v", "k_scale", "v_scale",
                 "nbytes", "ts")

    def __init__(self, tokens, start_pos, k, v, k_scale=None,
                 v_scale=None):
        self.tokens = tokens
        self.start_pos = start_pos
        self.k = k                      # np [L, n_pages, page, KV·HD]
        self.v = v
        # int8 entries (ISSUE 13): fp32 [L, n_pages, KV, page] — the
        # scales travel WITH the pages through every tier move
        self.k_scale = k_scale
        self.v_scale = v_scale
        from quoracle_tpu.models.quant import entry_nbytes
        self.nbytes = entry_nbytes(k, v, k_scale, v_scale)
        self.ts = time.monotonic()


class _HostBlock:
    __slots__ = ("tokens", "k", "v", "k_scale", "v_scale", "nbytes",
                 "ts")

    def __init__(self, tokens, k, v, k_scale=None, v_scale=None):
        self.tokens = tokens            # full token prefix (page-aligned)
        self.k = k                      # np [L, page, KV·HD]
        self.v = v
        self.k_scale = k_scale          # np [L, KV, page] (int8 entries)
        self.v_scale = v_scale
        from quoracle_tpu.models.quant import entry_nbytes
        self.nbytes = entry_nbytes(k, v, k_scale, v_scale)
        self.ts = time.monotonic()


class HostPageStore:
    """LRU-bounded host-RAM page store: hibernated sessions + stripped
    prefix blocks. Session entries DROP on budget pressure (they are one
    agent's private state — re-prefill recovers them); prefix blocks SPILL
    to disk first when a DiskPrefixStore is attached (they are shared,
    reconstructible state worth keeping cheap)."""

    def __init__(self, budget_bytes: int, model: str = ""):
        self.budget_bytes = int(budget_bytes)
        self.model = model
        self.sessions: OrderedDict[str, _HostSession] = OrderedDict()
        self.prefixes: OrderedDict[str, _HostBlock] = OrderedDict()
        self.bytes = 0
        self.evicted_sessions = 0
        self.evicted_prefixes = 0

    def _charge(self, n: int) -> None:
        self.bytes += n

    def put_session(self, key: str, entry: _HostSession,
                    spill_fn=None) -> None:
        old = self.sessions.pop(key, None)
        if old is not None:
            self.bytes -= old.nbytes
        self.sessions[key] = entry
        self._charge(entry.nbytes)
        self.shrink(spill_fn)

    def put_prefix(self, key: str, entry: _HostBlock,
                   spill_fn=None) -> None:
        if key in self.prefixes:
            return
        self.prefixes[key] = entry
        self._charge(entry.nbytes)
        self.shrink(spill_fn)

    def pop_session(self, key: str) -> Optional[_HostSession]:
        e = self.sessions.pop(key, None)
        if e is not None:
            self.bytes -= e.nbytes
        return e

    def get_prefix(self, key: str) -> Optional[_HostBlock]:
        e = self.prefixes.get(key)
        if e is not None:
            self.prefixes.move_to_end(key)
            e.ts = time.monotonic()
        return e

    def shrink(self, spill_fn=None) -> None:
        """Evict LRU entries until under budget. Prefix blocks go first
        (disk-spillable via ``spill_fn``; sessions are irreplaceable until
        their owner re-prefills), oldest-first within each kind."""
        from quoracle_tpu.infra.telemetry import KV_HOST_EVICTIONS_TOTAL
        while self.bytes > self.budget_bytes and self.prefixes:
            key, e = self.prefixes.popitem(last=False)
            self.bytes -= e.nbytes
            self.evicted_prefixes += 1
            KV_HOST_EVICTIONS_TOTAL.inc(model=self.model, kind="prefix")
            if spill_fn is not None:
                spill_fn(key, e)
        while self.bytes > self.budget_bytes and self.sessions:
            _, e = self.sessions.popitem(last=False)
            self.bytes -= e.nbytes
            self.evicted_sessions += 1
            KV_HOST_EVICTIONS_TOTAL.inc(model=self.model, kind="session")

    def headroom(self) -> int:
        return max(0, self.budget_bytes - self.bytes)

    def stats(self) -> dict:
        return {
            "budget_bytes": self.budget_bytes,
            "bytes": self.bytes,
            "sessions": len(self.sessions),
            "prefix_blocks": len(self.prefixes),
            "evicted_sessions": self.evicted_sessions,
            "evicted_prefixes": self.evicted_prefixes,
        }


class DiskPrefixStore:
    """Checksummed on-disk store of page-aligned prefix blocks, one file
    per block keyed by the content hash of the token prefix ending at the
    block. Files are ``.npz`` (tokens, k, v, crc) written atomically
    (tmp + rename — a torn write is an unreadable tmp file, never a
    half-entry) under ``<root>/<model-shape-signature>/``, so engines of
    different geometry or dtype can never load each other's bytes.

    ``load`` verifies the crc32 of the payload against the stored value
    and the requested token prefix against the stored one; any mismatch
    counts as corrupt, unlinks the file, and returns None — the caller
    falls back to a plain prefill. The store is an OPTIMIZATION with a
    paranoid boundary, never a correctness dependency.

    Bounded: ``budget_bytes`` (0 = unbounded) caps the directory —
    when a save overflows it, oldest-mtime entries unlink until the
    store fits again, and ``load`` touches an entry's mtime so pruning
    approximates LRU rather than FIFO. Directory size is tracked
    incrementally (one startup scan, refreshed at most every
    ``_SCAN_TTL_S``), so a /api/resources scrape costs no listdir."""

    _SCAN_TTL_S = 30.0

    def __init__(self, root: str, signature: str, model: str = "",
                 budget_bytes: int = 0):
        self.dir = os.path.join(root, signature)
        self.model = model
        self.budget_bytes = int(budget_bytes)
        os.makedirs(self.dir, exist_ok=True)
        self.writes = 0
        self.loads = 0
        self.corrupt = 0
        self.pruned = 0
        self._lock = named_lock("tier.disk")
        self._scan_entries = 0
        self._scan_bytes = 0
        self._scan_ts = 0.0
        with self._lock:
            self._rescan_locked()         # one startup scan; then cached

    def _rescan_locked(self) -> None:
        entries = nbytes = 0
        try:
            # TTL-bounded (30 s) accounting scan of this store's own
            # directory, under its own leaf lock — nothing on the
            # serving path contends for it during the walk.
            # qlint: allow[lock-blocking] TTL-bounded scan under the store's leaf lock
            for f in os.listdir(self.dir):
                if not f.endswith(".npz"):
                    continue
                entries += 1
                try:
                    nbytes += os.path.getsize(os.path.join(self.dir, f))
                except OSError:
                    pass
        except OSError:
            pass
        self._scan_entries, self._scan_bytes = entries, nbytes
        self._scan_ts = time.monotonic()

    def _prune_locked(self) -> None:
        """Unlink oldest-mtime entries until the store fits the budget
        (load() touches mtime, so eviction order approximates LRU)."""
        files = []
        try:
            # budget enforcement IS the lock's job: the prune must see a
            # stable ledger, and it only runs on the (async) spill
            # writer when a save overflows the byte budget.
            # qlint: allow[lock-blocking] budget prune on the spill writer, leaf lock
            for f in os.listdir(self.dir):
                if not f.endswith(".npz"):
                    continue
                p = os.path.join(self.dir, f)
                try:
                    stt = os.stat(p)
                except OSError:
                    continue
                files.append((stt.st_mtime, stt.st_size, p))
        except OSError:
            return
        files.sort()
        self._scan_entries = len(files)
        self._scan_bytes = sum(sz for _, sz, _ in files)
        self._scan_ts = time.monotonic()
        for _, sz, p in files:
            if self._scan_bytes <= self.budget_bytes:
                break
            try:
                os.unlink(p)
            except OSError:
                continue
            self._scan_bytes -= sz
            self._scan_entries -= 1
            self.pruned += 1

    @staticmethod
    def block_key(tokens: Sequence[int]) -> str:
        h = hashlib.sha256(
            np.asarray(tokens, np.int64).tobytes()).hexdigest()
        return h[:40]

    def _path(self, key: str) -> str:
        return os.path.join(self.dir, f"{key}.npz")

    def has(self, key: str) -> bool:
        return os.path.exists(self._path(key))

    @staticmethod
    def _crc(tokens: np.ndarray, k: np.ndarray, v: np.ndarray,
             k_scale: Optional[np.ndarray] = None,
             v_scale: Optional[np.ndarray] = None) -> int:
        c = zlib.crc32(tokens.tobytes())
        c = zlib.crc32(k.tobytes(), c)
        c = zlib.crc32(v.tobytes(), c)
        if k_scale is not None:
            # int8 entries (ISSUE 13): the per-page scale blocks live
            # under the SAME crc as the payload — a flipped scale byte
            # is indistinguishable from a flipped payload byte at this
            # boundary (reject, unlink, degrade to re-prefill)
            c = zlib.crc32(np.ascontiguousarray(k_scale).tobytes(), c)
            c = zlib.crc32(np.ascontiguousarray(v_scale).tobytes(), c)
        return c & 0xFFFFFFFF

    def save(self, key: str, tokens: Sequence[int], k: np.ndarray,
             v: np.ndarray, k_scale: Optional[np.ndarray] = None,
             v_scale: Optional[np.ndarray] = None) -> bool:
        """Write one block. The npz serialization and the tmp-file write
        run OUTSIDE ``_lock`` (qlint lock-blocking: the spill writer
        holding the lock through megabytes of compression would stall
        every stats()/load() accounting touch for the duration); only
        the atomic publish (rename) and the size accounting + budget
        prune run under it. Two writers racing the same content-
        addressed key both produce identical bytes under distinct tmp
        names, and the exists-check under the lock keeps the accounting
        single-counted."""
        path = self._path(key)
        if os.path.exists(path):
            return False                 # content-addressed: already there
        toks = np.asarray(tokens, np.int64)
        tmp = f"{path}.tmp.{os.getpid()}.{threading.get_ident()}"
        try:
            with open(tmp, "wb") as f:
                # KV payloads ship as RAW BYTES + dtype name + shape:
                # npz round-trips extension dtypes (ml_dtypes
                # bfloat16 — the serving cache dtype) as an opaque
                # void dtype, which would silently strip the dtype a
                # restore needs. Int8 entries (ISSUE 13) append their
                # per-page scale arrays under the same crc.
                extra = {}
                if k_scale is not None:
                    extra = {
                        "k_scale": np.ascontiguousarray(
                            k_scale, np.float32),
                        "v_scale": np.ascontiguousarray(
                            v_scale, np.float32),
                        "scale_shape": np.asarray(k_scale.shape),
                    }
                np.savez(
                    f, tokens=toks,
                    k=np.ascontiguousarray(k).view(np.uint8)
                    .reshape(-1),
                    v=np.ascontiguousarray(v).view(np.uint8)
                    .reshape(-1),
                    dtype=str(k.dtype), shape=np.asarray(k.shape),
                    crc=np.uint32(self._crc(toks, k, v, k_scale,
                                            v_scale)),
                    **extra)
            with self._lock:
                if os.path.exists(path):
                    # a concurrent writer published the same content
                    # first: drop ours, count nothing
                    os.unlink(tmp)
                    return False
                # atomic publish: one rename + one stat under the
                # store's own leaf lock keeps the size ledger exact; the
                # payload write already happened outside.
                # qlint: allow[lock-blocking] single rename, not payload I/O
                os.replace(tmp, path)
                self._scan_entries += 1
                try:
                    self._scan_bytes += os.path.getsize(path)
                except OSError:
                    pass                  # bytes drift; TTL heal below
                # TTL healing rescan moved OFF the scrape path (ISSUE
                # 16): stats() is a pure O(1) snapshot now (a 100k-
                # session replay scrapes /api/kv concurrently), so any
                # accounting drift heals here on the spill writer —
                # which is already doing disk I/O — at most once per
                # TTL window.
                if (time.monotonic() - self._scan_ts
                        > self._SCAN_TTL_S):
                    self._rescan_locked()
                if (self.budget_bytes
                        and self._scan_bytes > self.budget_bytes):
                    self._prune_locked()
            self.writes += 1
            return True
        except OSError:
            logger.exception("disk prefix write failed: %s", path)
            try:
                os.unlink(tmp)
            except OSError:
                pass
            return False

    def load(self, key: str,
             tokens: Sequence[int]) -> Optional[tuple[np.ndarray,
                                                      np.ndarray]]:
        path = self._path(key)
        if not os.path.exists(path):
            return None
        # Chaos seam (ISSUE 11): a "corrupt" directive flips bytes in
        # the FILE before the normal load path runs, so the crc32
        # boundary below is what catches it — end-to-end proof that a
        # torn/rotted entry is skipped, unlinked, and never served.
        from quoracle_tpu.chaos.faults import CHAOS
        d = CHAOS.fire("kvtier.disk_load", model=self.model)
        if d is not None and d.kind == "corrupt":
            self._chaos_corrupt(path)
        # Chaos seam (ISSUE 13): "kvtier.scale_corrupt" flips a byte in
        # the TAIL of the entry file — where npz appends the int8
        # entry's per-page scale arrays — on the restore path. The crc
        # covers scales exactly like payload, so the SAME boundary must
        # reject it: a silently-wrong scale would dequantize every
        # token of the page to wrong values at temp 0.
        d = CHAOS.fire("kvtier.scale_corrupt", model=self.model)
        if d is not None and d.kind == "corrupt":
            self._chaos_corrupt(path, where=0.95)
        try:
            # Restore path by design (ARCHITECTURE §9): extend_prefix
            # calls this under the store lock so match→alloc→scatter→
            # insert stays atomic against concurrent alloc; the disk
            # read is the price of a restore and is tracked by
            # quoracle_kv_restore_ms. Sessioned callers already hold
            # the engine's paged lock, so no decode work is stalled
            # that wasn't already waiting on this restore.
            # qlint: allow[lock-blocking] restore reads under the store lock by design
            with np.load(path) as z:
                toks, crc = z["tokens"], int(z["crc"])
                dt = jax.numpy.dtype(str(z["dtype"]))
                shape = tuple(int(s) for s in z["shape"])
                k = z["k"].view(dt).reshape(shape)
                v = z["v"].view(dt).reshape(shape)
                ks = vs = None
                if "k_scale" in z.files:
                    sshape = tuple(int(s) for s in z["scale_shape"])
                    ks = np.asarray(z["k_scale"],
                                    np.float32).reshape(sshape)
                    vs = np.asarray(z["v_scale"],
                                    np.float32).reshape(sshape)
            if (self._crc(toks, k, v, ks, vs) != crc
                    or toks.tolist() != [int(t) for t in tokens]):
                raise ValueError("checksum/token mismatch")
            self.loads += 1
            try:
                # qlint: allow[lock-blocking] one-syscall LRU touch on the restore path
                os.utime(path)            # LRU touch for budget pruning
            except OSError:
                pass
            from quoracle_tpu.infra.telemetry import KV_DISK_LOADS_TOTAL
            KV_DISK_LOADS_TOTAL.inc(model=self.model, status="ok")
            return (k, v) if ks is None else (k, v, ks, vs)
        except Exception:                 # noqa: BLE001 — corrupt entry
            self.corrupt += 1
            logger.warning("corrupt disk prefix entry skipped: %s", path)
            from quoracle_tpu.infra.flightrec import FLIGHT
            from quoracle_tpu.infra.telemetry import KV_DISK_LOADS_TOTAL
            KV_DISK_LOADS_TOTAL.inc(model=self.model, status="corrupt")
            FLIGHT.record("kv_disk_corrupt", path=path, model=self.model)
            # exact incremental accounting (ISSUE 16): decrement the
            # ledger by the unlinked entry instead of invalidating the
            # whole scan — stats() never pays a rescan for a corrupt
            # eviction
            try:
                sz = os.path.getsize(path)
                os.unlink(path)
            except OSError:
                sz = -1
            if sz >= 0:
                with self._lock:
                    self._scan_entries = max(0, self._scan_entries - 1)
                    self._scan_bytes = max(0, self._scan_bytes - sz)
            return None

    @staticmethod
    def _chaos_corrupt(path: str, where: float = 0.5) -> None:
        """Flip a byte in place at fraction ``where`` of the file
        (chaos "corrupt" directives: 0.5 lands mid-payload;
        kvtier.scale_corrupt uses 0.95 to land in the appended scale
        arrays of an int8 entry). Best-effort: a vanished file is
        already the degraded case."""
        try:
            # qlint: allow[lock-blocking] chaos-only byte flip; armed plans never run on the production hot path
            with open(path, "r+b") as f:
                f.seek(0, os.SEEK_END)
                size = f.tell()
                if size < 1:
                    return
                pos = min(size - 1, int(size * where))
                f.seek(pos)
                b = f.read(1)
                f.seek(pos)
                f.write(bytes([b[0] ^ 0xFF]) if b else b"\xff")
        except OSError:
            pass

    def stats(self) -> dict:
        # O(1) by contract (ISSUE 16): the entry/byte ledger is
        # maintained incrementally by save()/load()/prune, and the TTL
        # healing rescan runs on the save path — a scrape NEVER walks
        # the directory (tests/test_sim.py bounds this at 100k-entry
        # scale)
        with self._lock:
            return {"dir": self.dir, "entries": self._scan_entries,
                    "bytes": self._scan_bytes,
                    "budget_bytes": self.budget_bytes,
                    "writes": self.writes, "loads": self.loads,
                    "corrupt_skipped": self.corrupt,
                    "pruned": self.pruned}


class TierManager:
    """The tier ladder for one engine's SessionStore. Attached via
    ``GenerateEngine.attach_tier`` (which wires ``store.tier = self``);
    every method that touches the device pool assumes the engine's
    ``_paged_lock`` discipline described in the module docstring."""

    def __init__(self, store, model: str = "", host_mb: int = 256,
                 disk_dir: Optional[str] = None, paged_lock=None,
                 signature: Optional[str] = None,
                 disk_gb: float = 8.0):
        self.store = store
        self.model = model
        self.paged_lock = paged_lock
        self.signature = signature or (model.replace("/", "_")
                                       or "default")
        self.host = HostPageStore(int(host_mb) * (1 << 20), model=model)
        self.disk: Optional[DiskPrefixStore] = None
        if disk_dir:
            self.disk = DiskPrefixStore(
                disk_dir, self.signature, model=model,
                budget_bytes=int(disk_gb * (1 << 30)))
        # Fleet prefix service (ISSUE 12, serving/fabric/prefixd.py):
        # a read-through client attached via attach_prefixd — the
        # restore ladder's last rung (host → disk → FLEET) and the
        # spill writer's second publish target.
        self.prefixd = None
        # monotonic counters (stats() → /api/kv)
        self.demoted_sessions = 0
        self.demoted_prefix_pages = 0
        self.restored_sessions = 0
        self.restored_prefix_pages = 0
        self.restore_failures = 0
        self.spill_drops = 0
        # Disk spills are ASYNC: the eviction ladder runs inside
        # SessionStore.alloc with the store lock held (and the engine's
        # paged lock, for sessioned callers) — an npz write there would
        # stall every allocation under memory pressure. Only the
        # host-side numpy copy happens under the locks; writes queue to
        # a daemon writer thread. Best-effort by design: a full queue
        # drops the spill (the block is reconstructible by prefill).
        self._spill_q: Optional[queue.Queue] = None
        if self.disk is not None:
            self._ensure_spill_writer()

    def _ensure_spill_writer(self) -> None:
        if self._spill_q is None:
            self._spill_q = queue.Queue(maxsize=512)
            threading.Thread(
                target=self._spill_loop, daemon=True,
                name=f"kvtier-spill-{self.model or 'default'}").start()

    def attach_prefixd(self, client) -> None:
        """Wire the fleet prefix-service client (ISSUE 12): reads join
        extend_prefix's restore ladder, writes ride the async spill
        writer (wire I/O never happens under the serving locks)."""
        self.prefixd = client
        self._ensure_spill_writer()

    # -- device <-> host plumbing ---------------------------------------

    def _gather_host(self, pages: list[int]) -> tuple:
        """One device_get per victim: the pages' KV as host numpy —
        (k, v, k_scale, v_scale), scales None on unquantized pools.

        Deliberately under the store lock (ARCHITECTURE §9 demote
        invariant): eviction-as-demotion must copy the victim's pages
        before alloc's ladder releases them, or a concurrent writer
        could scribble the pool pages mid-copy. One victim per
        device_get bounds the stall; the async spill queue keeps DISK
        out of this window."""
        import jax
        st = self.store
        idx = np.asarray(pages, np.int32)
        # qlint: allow[hot-path-sync, lock-blocking] demote copies one victim under the store lock by design
        k = np.asarray(jax.device_get(st.k[:, idx]))
        # qlint: allow[hot-path-sync, lock-blocking] second half of the same bounded victim copy
        v = np.asarray(jax.device_get(st.v[:, idx]))
        if st.k_scale is None:
            return k, v, None, None
        # qlint: allow[hot-path-sync, lock-blocking] scale blocks ride the same bounded victim copy
        ks = np.asarray(jax.device_get(st.k_scale[:, idx]))
        # qlint: allow[hot-path-sync, lock-blocking] scale blocks ride the same bounded victim copy
        vs = np.asarray(jax.device_get(st.v_scale[:, idx]))
        return k, v, ks, vs

    def _scatter_device(self, pages: list[int], k: np.ndarray,
                        v: np.ndarray, k_scale=None,
                        v_scale=None) -> None:
        """Page-in via the pool scatter (shape-bucketed to bound
        compiles: the page-count axis pads to a power of two, padded
        slots target scratch page 0). Int8 pools scatter the scale
        blocks beside the payload pages."""
        import jax.numpy as jnp
        st = self.store
        n = len(pages)
        cap = _round_up_pow2(max(1, n))
        k, v = _as_stored(k), _as_stored(v)
        if cap != n:
            pad = ((0, 0), (0, cap - n), (0, 0), (0, 0))
            k = np.pad(k, pad)
            v = np.pad(v, pad)
            if k_scale is not None:
                spad = ((0, 0), (0, cap - n), (0, 0), (0, 0))
                k_scale = np.pad(k_scale, spad)
                v_scale = np.pad(v_scale, spad)
        idx = np.zeros((cap,), np.int32)
        idx[:n] = pages
        if st.k_scale is not None:
            if k_scale is None:
                # entry predates quantization (or scales were lost):
                # never scatter int8 payloads with stale scales — the
                # caller degrades to re-prefill
                raise ValueError(
                    "quantized pool restore without scale blocks")
            (st.k, st.v, st.k_scale,
             st.v_scale) = _scatter_pages_q(
                st.k, st.v, st.k_scale, st.v_scale, jnp.asarray(k),
                jnp.asarray(v), jnp.asarray(k_scale),
                jnp.asarray(v_scale), jnp.asarray(idx))
            return
        st.k, st.v = _scatter_pages(st.k, st.v, jnp.asarray(k),
                                    jnp.asarray(v), jnp.asarray(idx))

    # -- session hibernation --------------------------------------------

    def demote_session(self, key: str, sess) -> bool:
        """Copy a victim session's KV host-side before its pages release
        (called from SessionStore.alloc's ladder, both locks held). The
        caller still releases the pages — refcounted sharing is preserved
        because only the VICTIM's references drop; adopters and the radix
        tree keep the resident copies they already hold."""
        st = self.store
        pages = [p for p in sess.pages if p]
        if not pages or st.k is None:
            return False
        t0 = time.monotonic()
        try:
            k, v, ks, vs = self._gather_host(pages)
        except Exception:                 # noqa: BLE001 — demote is best-
            logger.exception("kv demote failed for %s", key)   # effort
            return False
        entry = _HostSession(list(sess.tokens), sess.start_pos, k, v,
                             ks, vs)
        self.host.put_session(key, entry,
                              spill_fn=self._spill_prefix_entry)
        self.demoted_sessions += 1
        from quoracle_tpu.infra.flightrec import FLIGHT
        from quoracle_tpu.infra.telemetry import KV_DEMOTES_TOTAL
        KV_DEMOTES_TOTAL.inc(model=self.model, kind="session")
        self._note_bytes_saved("demote", entry)
        FLIGHT.record("kv_demote", model=self.model, what="session",
                      session=key, pages=len(pages),
                      ms=round((time.monotonic() - t0) * 1000, 2))
        return True

    def export_session(self, key: str) -> Optional[_HostSession]:
        """Page-export seam for cross-replica KV handoff (ISSUE 10,
        serving/handoff.py): hibernate the session OUT of this engine —
        exactly the eviction ladder's demote (one device_get, refcounted
        release, adopters/radix readers untouched) — and hand the host
        copy to the caller instead of parking it in this tier's store.
        A session already hibernated is handed over directly. Returns
        None when the session exists nowhere (caller re-prefills on the
        destination — always correct). Assumes the engine's paged lock
        is held, like every pool-touching method here."""
        st = self.store
        with st.lock:
            sess = st._sessions.get(key)
            if sess is None:
                return self.host.pop_session(key)
            if not self.demote_session(key, sess):
                return None
            del st._sessions[key]
            st._release(sess.pages)
            return self.host.pop_session(key)

    def adopt_session(self, key: str, entry: _HostSession) -> None:
        """Page-adopt seam (ISSUE 10): accept a handed-off host-side
        session copy into THIS tier's host store, after which the normal
        restore machinery (restore_session via prefetch or the engine's
        session lookup) pages it in — "hibernate on the prefill replica,
        restore on the decode replica". Replaces any stale copy under
        the same key; the live store is untouched (the caller drops or
        never had a resident session under this key)."""
        with self.store.lock:
            self.host.put_session(key, entry,
                                  spill_fn=self._spill_prefix_entry)

    def has_session(self, key: str) -> bool:
        return key in self.host.sessions

    def peek_tokens(self, key: str) -> Optional[list]:
        e = self.host.sessions.get(key)
        return list(e.tokens) if e is not None else None

    def discard_session(self, key: str) -> None:
        """The live store replaced or dropped this session — the host
        copy is stale and must never restore over fresher state."""
        self.host.pop_session(key)

    def restore_session(self, key: str):
        """Page a hibernated session back into the pool and re-register
        it. Returns the live session or None (pool unattainable / entry
        gone — the caller re-prefills, which is always correct). Assumes
        the engine's paged lock is held."""
        # Chaos seam (ISSUE 11): a "fail" directive exercises the
        # degrade-to-re-prefill path the docstring promises — the entry
        # stays in the host tier (a later touch may still restore it),
        # only THIS restore reports failure.
        from quoracle_tpu.chaos.faults import CHAOS
        d = CHAOS.fire("kvtier.restore", model=self.model)
        if d is not None and d.kind == "fail":
            self.restore_failures += 1
            return None
        st = self.store
        with st.lock:
            e = self.host.sessions.get(key)
            if e is None:
                return None
            n = e.k.shape[1]
            pages = st.alloc(n, protect=(key,))
            if pages is None:
                self.restore_failures += 1
                return None
            e = self.host.pop_session(key)
            if e is None:                 # raced a discard
                st._release(pages)
                return None
            t0 = time.monotonic()
            try:
                self._scatter_device(pages, e.k, e.v, e.k_scale,
                                     e.v_scale)
            except ValueError:
                # dtype/scale skew (a non-quantized entry adopted into a
                # quantized pool): degrade to re-prefill, never scatter
                # wrong bytes
                st._release(pages)
                self.restore_failures += 1
                return None
            sess = st.register_restored(key, list(e.tokens), pages,
                                        e.start_pos)
            self.restored_sessions += 1
            ms = (time.monotonic() - t0) * 1000
        from quoracle_tpu.infra.flightrec import FLIGHT
        from quoracle_tpu.infra.telemetry import (
            KV_RESTORE_MS, KV_RESTORES_TOTAL,
        )
        KV_RESTORES_TOTAL.inc(model=self.model, kind="session",
                              source="host")
        KV_RESTORE_MS.observe(ms, model=self.model, kind="session")
        from quoracle_tpu.infra import costobs, introspect
        costobs.charge_restore(self.model, ms, source="host")
        # wait-state + heartbeat (ISSUE 18): the restore wall waits on
        # the DISPATCHING thread, so the batcher books it against the
        # step's rows; bytes feed the kv.restore liveness counter
        introspect.note_restore(ms, nbytes=int(e.k.nbytes)
                                + int(e.v.nbytes))
        FLIGHT.record("kv_restore", model=self.model, what="session",
                      session=key, pages=len(pages), ms=round(ms, 2))
        from quoracle_tpu.infra.telemetry import TRACER
        if TRACER.active():
            # the restore leg of a hibernated/handed-off session enters
            # the session's trace (ISSUE 15) — under the store lock's
            # caller, so a retroactive emit, never a bound span
            TRACER.emit("kv.restore", ms, ts=time.time() - ms / 1000.0,
                        session=key, model=self.model,
                        pages=len(pages))
        return sess

    # -- prefix-block tiering -------------------------------------------

    def _block_key(self, tokens: Sequence[int]) -> str:
        return DiskPrefixStore.block_key(tokens)

    def _spill_loop(self) -> None:
        while True:
            key, entry = self._spill_q.get()
            try:
                self._write_block(key, entry)
            except Exception:             # noqa: BLE001 — best-effort
                logger.exception("kv disk spill failed")
            finally:
                self._spill_q.task_done()

    def _note_bytes_saved(self, tier: str, entry) -> None:
        """Quantized byte-economy accounting (ISSUE 13): each tier move
        of an int8 entry counts the bf16-equivalent bytes it avoided
        holding/shipping (2·payload − (payload + scales)). No-op for
        unquantized entries."""
        if np.dtype(entry.k.dtype) != np.int8:
            return
        from quoracle_tpu.infra.telemetry import QUANT_BYTES_SAVED_TOTAL
        payload = int(entry.k.nbytes) + int(entry.v.nbytes)
        QUANT_BYTES_SAVED_TOTAL.inc(max(0, 2 * payload - entry.nbytes),
                                    model=self.model, tier=tier)

    def _write_block(self, key: str, entry: _HostBlock) -> None:
        """Writer-thread side of a spill: the actual (atomic, content-
        addressed) disk write — and, with a fleet prefix service
        attached, the publish to it — never under the store/paged
        locks."""
        if self.disk is not None \
                and self.disk.save(key, entry.tokens, entry.k, entry.v,
                                   entry.k_scale, entry.v_scale):
            from quoracle_tpu.infra.flightrec import FLIGHT
            from quoracle_tpu.infra.telemetry import KV_DISK_SPILLS_TOTAL
            KV_DISK_SPILLS_TOTAL.inc(model=self.model)
            FLIGHT.record("kv_disk_spill", model=self.model,
                          tokens=len(entry.tokens))
            self._note_bytes_saved("disk_spill", entry)
        if self.prefixd is not None:
            self.prefixd.publish(key, entry.tokens, entry.k, entry.v,
                                 entry.k_scale, entry.v_scale)

    def _enqueue_spill(self, key: str, entry: _HostBlock) -> None:
        if self._spill_q is None:
            return
        try:
            self._spill_q.put_nowait((key, entry))
        except queue.Full:
            self.spill_drops += 1

    def flush_spills(self) -> None:
        """Block until every queued disk write has landed (tests and
        orderly shutdown; the serving path never needs to wait)."""
        if self._spill_q is not None:
            self._spill_q.join()

    def _spill_prefix_entry(self, key: str, entry: _HostBlock) -> None:
        """Host-budget eviction of a prefix block: queue a disk spill
        when attached (dedup by content key at write time), else the
        block is simply gone. Runs under the store lock — must not
        touch the filesystem."""
        self._enqueue_spill(key, entry)

    def capture_leaf(self, tokens: Sequence[int], page: int) -> None:
        """A radix-cache leaf is about to be stripped (prefix_cache.evict):
        keep its block alive in the host tier instead of recomputing it
        later. Called under the store lock (and the paged lock, via
        alloc)."""
        st = self.store
        if st.k is None:
            return
        key = self._block_key(tokens)
        if key in self.host.prefixes:
            return
        if self.disk is not None and self.disk.has(key):
            return        # already durable; skip the device_get
        try:
            k, v, ks, vs = self._gather_host([page])
        except Exception:                 # noqa: BLE001 — best-effort
            logger.exception("prefix leaf capture failed")
            return
        self.host.put_prefix(
            key, _HostBlock(list(tokens), k[:, 0], v[:, 0],
                            None if ks is None else ks[:, 0],
                            None if vs is None else vs[:, 0]),
            spill_fn=self._spill_prefix_entry)
        self.demoted_prefix_pages += 1
        from quoracle_tpu.infra.flightrec import FLIGHT
        from quoracle_tpu.infra.telemetry import KV_DEMOTES_TOTAL
        KV_DEMOTES_TOTAL.inc(model=self.model, kind="prefix")
        FLIGHT.record("kv_demote", model=self.model, what="prefix",
                      tokens=len(tokens))

    def persist_block(self, tokens: Sequence[int], page: int) -> None:
        """Insert-time disk persistence: a block newly cached in the
        radix tree is written through to disk (content-addressed — a
        block already persisted costs one stat()). This is what makes a
        restarted process warm: the disk store accumulates the fleet's
        hot prefixes while they are still hot, not only at eviction.
        Only the device→host copy happens here (the caller holds the
        store lock, so the page content is stable); the npz write rides
        the spill queue."""
        if self.disk is None and self.prefixd is None:
            return
        key = self._block_key(tokens)
        if self.disk is not None and self.disk.has(key):
            return
        st = self.store
        if st.k is None:
            return
        try:
            k, v, ks, vs = self._gather_host([page])
        except Exception:                 # noqa: BLE001 — best-effort
            return
        self._enqueue_spill(
            key, _HostBlock([int(t) for t in tokens], k[:, 0], v[:, 0],
                            None if ks is None else ks[:, 0],
                            None if vs is None else vs[:, 0]))

    def extend_prefix(self, tokens: Sequence[int], cap: int) -> int:
        """Lazily page tiered prefix blocks back into the radix tree:
        while the tree's page-aligned match of ``tokens`` can be extended
        by a block held in the host or disk tier, alloc a page, scatter
        the block in, and insert it. Returns blocks restored. Called from
        SessionStore.match_prefix under the store lock (paged lock held
        by the sessioned caller)."""
        st = self.store
        if st.k is None:
            return 0
        page = st.page
        restored = 0
        attempted: set = set()
        shrinks = 0
        while True:
            j = st.prefix_cache.match_len(tokens, cap) // page
            end = (j + 1) * page
            if end > min(len(tokens), cap):
                break
            prefix = [int(t) for t in tokens[:end]]
            key = self._block_key(prefix)
            if key in attempted:
                break                     # do not thrash a tiny pool
            attempted.add(key)
            blk = self.host.get_prefix(key)
            source = "host"
            if blk is None and self.disk is not None:
                loaded = self.disk.load(key, prefix)
                if loaded is not None:
                    blk = _HostBlock(prefix, *loaded)
                    source = "disk"
            if blk is None and self.prefixd is not None:
                # The fleet rung (ISSUE 12): same restore-path-by-design
                # argument as the disk read above — sessioned callers
                # already hold the paged lock waiting on this restore,
                # and the fetch degrades to a miss on any failure.
                # qlint: allow[lock-blocking] fleet prefix fetch on the restore path by design
                fetched = self.prefixd.fetch(key, prefix)
                if fetched is not None:
                    blk = _HostBlock(prefix, *fetched)
                    source = "prefixd"
            if blk is None:
                break
            pages = st.alloc(1)
            if pages is None:
                break
            path = st.prefix_cache._walk(tokens, cap)
            if len(path) != j:
                # alloc's eviction ladder strips radix leaves first and
                # match_len bumps no LRU stamps, so it can take the
                # deepest node of the very path just matched. Inserting
                # at depth j would then label this block's KV with block
                # j-1's tokens and serve wrong bytes at temp 0. Release
                # and restart from a fresh match (bounded: a pool too
                # small to hold the chain oscillates, so give up after a
                # few shrinks instead of thrashing).
                st._release(pages)
                attempted.discard(key)
                shrinks += 1
                if shrinks > 8:
                    break
                continue
            t0 = time.monotonic()
            try:
                self._scatter_device(
                    pages, blk.k[:, None], blk.v[:, None],
                    None if blk.k_scale is None else blk.k_scale[:, None],
                    None if blk.v_scale is None else blk.v_scale[:, None])
            except ValueError:
                # scale-less block against a quantized pool (signature
                # dirs make this near-impossible; stay paranoid anyway)
                st._release(pages)
                self.restore_failures += 1
                break
            added = st.prefix_cache.insert(
                prefix, [nd.page for nd in path] + pages)
            if not added:
                st._release(pages)        # raced an insert; keep theirs
                continue
            # Drop alloc's base reference: the tree's reference must be
            # the ONLY holder of a restored block (store-back reaches
            # the same state when the inserting session later drops).
            # Keeping the base ref pins the page at refcount 2 forever —
            # _evictable_leaf needs exactly 1 — and a restart-warmed
            # process would steadily lose pool capacity.
            st._release(pages)
            restored += 1
            self.restored_prefix_pages += 1
            ms = (time.monotonic() - t0) * 1000
            from quoracle_tpu.infra.telemetry import (
                KV_RESTORE_MS, KV_RESTORES_TOTAL,
            )
            KV_RESTORES_TOTAL.inc(model=self.model, kind="prefix",
                                  source=source)
            KV_RESTORE_MS.observe(ms, model=self.model, kind="prefix")
            from quoracle_tpu.infra import costobs, introspect
            costobs.charge_restore(self.model, ms, source=source)
            introspect.note_restore(ms, nbytes=int(blk.k.nbytes)
                                    + int(blk.v.nbytes))
        if restored:
            from quoracle_tpu.infra.flightrec import FLIGHT
            FLIGHT.record("kv_restore", model=self.model, what="prefix",
                          blocks=restored)
        return restored

    # -- reads -----------------------------------------------------------

    def demotable_bytes(self, page_bytes: int) -> int:
        """How many HBM bytes could move to the host tier right now
        without losing state. Exact, not optimistic: reuses alloc's
        attainability accounting over every resident session — victim-
        exclusive pages plus cache leaves that would strip once the
        victims' references drop. Pages held by in-flight adopters
        (acquire() without a registered session) stay resident and are
        NOT counted, so the QoS admission controller
        (serving/admission.py) never sees headroom the eviction ladder
        cannot deliver. Bounded by the host budget's remaining
        headroom."""
        st = self.store
        with st.lock:
            reclaimable = (st._attainable(list(st._sessions))
                           - len(st._free))
        return min(max(0, reclaimable) * page_bytes,
                   self.host.headroom())

    def stats(self) -> dict:
        return {
            "model": self.model,
            "host": self.host.stats(),
            "disk": self.disk.stats() if self.disk is not None else None,
            "demoted_sessions": self.demoted_sessions,
            "demoted_prefix_pages": self.demoted_prefix_pages,
            "restored_sessions": self.restored_sessions,
            "restored_prefix_pages": self.restored_prefix_pages,
            "restore_failures": self.restore_failures,
            "spill_queue": (self._spill_q.qsize()
                            if self._spill_q is not None else 0),
            "spill_drops": self.spill_drops,
            "prefixd": (self.prefixd.stats()
                        if self.prefixd is not None else None),
        }
