"""Tiered KV (serving/kvtier.py, ISSUE 7): host offload, session
hibernation, and the restart-surviving disk prefix store.

Covers the subsystem's acceptance bar end to end:
  * temp-0 BIT-EQUALITY of a hibernate→restore session against one that
    never left HBM (greedy and grammar-constrained rows);
  * COW/shared-page refcount integrity across demote/restore — demoting
    a donor must not disturb adopters or the radix tree, and a restored
    session diverging must still COW-swap;
  * kill-and-restart: a NEW engine over the same disk dir serves prefix
    hits from its predecessor's persisted blocks, and checksum-rejected
    corrupt entries are skipped (and unlinked), never served;
  * host-budget LRU eviction with prefix blocks spilling to disk;
  * the prefetch hook (engine.prefetch_session + ContinuousBatcher
    submit + backend.prefetch_sessions);
  * the QoS headroom signal counting demotable pages as reclaimable;
  * the formerly silent SessionStore.alloc drift branch now counting
    and flight-recording (ISSUE 7 satellite);
  * pool_sizing's per-tier capacity rows (ISSUE 7 satellite);
  * /api/kv + telemetry exposition.
"""

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from quoracle_tpu.models.config import get_model_config
from quoracle_tpu.models.generate import (
    GenerateEngine, SessionStore, _Session,
)
from quoracle_tpu.models.tokenizer import ByteTokenizer
from quoracle_tpu.models.transformer import init_params
from quoracle_tpu.serving.kvtier import DiskPrefixStore, TierManager

CFG = get_model_config("xla:tiny")
PARAMS = init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)


def make_engine(**kw):
    return GenerateEngine(CFG, PARAMS, ByteTokenizer(), max_seq=512,
                          prompt_buckets=(32, 64, 128, 256), **kw)


def enc(text):
    return ByteTokenizer().encode(text, add_bos=True)


def hibernate_all(engine):
    """Force the eviction ladder over every resident session: demand all
    usable pages (no protected keys), then release them."""
    st = engine.sessions
    with engine._paged_lock:
        with st.lock:
            got = st.alloc(st.n_pages - 1)
            assert got is not None
            st._release(got)


SYS = "system: " + "policy rules apply here. " * 8    # > 1 page of 128


# ---------------------------------------------------------------------------
# Hibernate → restore bit-equality
# ---------------------------------------------------------------------------

def test_hibernate_restore_greedy_bit_equal():
    tok = ByteTokenizer()
    p1 = enc(SYS + " task: count to five.")
    ctl = make_engine()
    a1 = ctl.generate([p1], temperature=0.0, max_new_tokens=24,
                      session_ids=["s"])
    p2 = p1 + a1[0].token_ids + tok.encode(" continue")
    a2 = ctl.generate([p2], temperature=0.0, max_new_tokens=24,
                      session_ids=["s"])

    eng = make_engine()
    tier = eng.attach_tier(host_mb=64)
    b1 = eng.generate([p1], temperature=0.0, max_new_tokens=24,
                      session_ids=["s"])
    assert b1[0].token_ids == a1[0].token_ids
    hibernate_all(eng)
    assert eng.sessions.get("s") is None
    assert tier.has_session("s")
    assert tier.demoted_sessions == 1
    # the splice layer still sees the conversation ids while hibernated
    assert eng.session_tokens("s") is not None
    b2 = eng.generate([p2], temperature=0.0, max_new_tokens=24,
                      session_ids=["s"])
    assert b2[0].token_ids == a2[0].token_ids
    assert tier.restored_sessions == 1
    # restore means PAGE-IN, not re-prefill: the cached-token count of
    # the resumed round matches the never-hibernated control exactly
    assert b2[0].n_cached_tokens == a2[0].n_cached_tokens > 0


def test_hibernate_restore_constrained_bit_equal():
    enum = ("wait", "send_message", "todo")
    p1 = enc(SYS + ' respond with an action json.')
    ctl = make_engine()
    a1 = ctl.generate([p1], temperature=0.0, max_new_tokens=48,
                      session_ids=["s"], constrain_json=[True],
                      action_enums=[enum])
    p2 = p1 + a1[0].token_ids + enc("again")[1:]
    a2 = ctl.generate([p2], temperature=0.0, max_new_tokens=48,
                      session_ids=["s"], constrain_json=[True],
                      action_enums=[enum])

    eng = make_engine()
    tier = eng.attach_tier(host_mb=64)
    b1 = eng.generate([p1], temperature=0.0, max_new_tokens=48,
                      session_ids=["s"], constrain_json=[True],
                      action_enums=[enum])
    assert b1[0].token_ids == a1[0].token_ids
    hibernate_all(eng)
    b2 = eng.generate([p2], temperature=0.0, max_new_tokens=48,
                      session_ids=["s"], constrain_json=[True],
                      action_enums=[enum])
    assert b2[0].token_ids == a2[0].token_ids
    assert tier.restored_sessions == 1


def test_restore_failure_falls_back_to_prefill():
    """A hibernated session whose restore cannot get pages re-prefills
    (correctness never depends on the tier) and the stale host copy is
    discarded at store-back."""
    eng = make_engine()
    tier = eng.attach_tier(host_mb=64)
    p1 = enc(SYS + " task A")
    ctl = make_engine()
    a1 = ctl.generate([p1], temperature=0.0, max_new_tokens=16,
                      session_ids=["s"])
    b1 = eng.generate([p1], temperature=0.0, max_new_tokens=16,
                      session_ids=["s"])
    hibernate_all(eng)
    # sabotage: empty the free list with a fake resident hog the ladder
    # cannot demote past (protect it at restore time via direct call)
    st = eng.sessions
    with st.lock:
        hog = st.alloc(len(st._free))
        assert hog
    with eng._paged_lock:
        assert tier.restore_session("s") is None   # unattainable
    assert tier.restore_failures == 1
    with st.lock:
        st._release(hog)
    # generate still answers correctly (restore now succeeds — pages are
    # back; equality with the control is the invariant either way)
    b2 = eng.generate([p1], temperature=0.0, max_new_tokens=16,
                      session_ids=["s"])
    assert b2[0].token_ids == a1[0].token_ids == b1[0].token_ids


# ---------------------------------------------------------------------------
# COW / shared-page refcount integrity across demote/restore
# ---------------------------------------------------------------------------

def test_shared_refcounts_survive_demote_restore():
    """Demoting a session whose prefix pages the radix tree (and an
    adopter) still reference must not free or corrupt those pages; the
    restored session gets FRESH pages and a later divergence COW-swaps
    exactly like an always-resident one."""
    tok = ByteTokenizer()
    eng = make_engine()
    tier = eng.attach_tier(host_mb=64)
    st = eng.sessions
    p_donor = enc(SYS + " donor task")
    d1 = eng.generate([p_donor], temperature=0.0, max_new_tokens=16,
                      session_ids=["donor"])
    donor_pages = list(st.get("donor").pages)
    # adopter shares the cached page-aligned SYS prefix
    p_adopt = enc(SYS + " adopter goes elsewhere")
    a1 = eng.generate([p_adopt], temperature=0.0, max_new_tokens=16,
                      session_ids=["adopter"])
    assert a1[0].n_cached_tokens >= st.page
    shared = [p for p in st.get("adopter").pages if p in donor_pages]
    assert shared, "adopter did not share the donor's prefix pages"
    with st.lock:
        refs_before = {p: st._refs.get(p, 1) for p in shared}

    # hibernate ONLY the donor (protect the adopter through the ladder)
    with eng._paged_lock:
        with st.lock:
            sess = st._sessions.pop("donor")
            assert tier.demote_session("donor", sess)
            st._release(sess.pages)
    # shared pages survive with exactly one reference fewer; the
    # adopter's session and the cache still read them
    with st.lock:
        for p in shared:
            assert st._refs.get(p, 1) == refs_before[p] - 1
            assert p not in st._free
    oracle = make_engine()
    o1 = oracle.generate([p_adopt], temperature=0.0, max_new_tokens=16,
                         session_ids=["x"])
    a2 = eng.generate([p_adopt], temperature=0.0, max_new_tokens=16,
                      session_ids=["adopter2"])
    assert a2[0].token_ids == o1[0].token_ids

    # restore the donor and DIVERGE it mid-shared-page: the adopter's
    # prefix must stay byte-intact (COW at the write site still fires)
    p_div = p_donor[:st.page // 2] + tok.encode("DIVERGENT " * 8)
    d2 = eng.generate([p_div], temperature=0.0, max_new_tokens=16,
                      session_ids=["donor"])
    assert tier.restored_sessions == 1
    o2 = oracle.generate([p_adopt], temperature=0.0, max_new_tokens=16,
                         session_ids=["y"])
    a3 = eng.generate([p_adopt], temperature=0.0, max_new_tokens=16,
                      session_ids=["adopter3"])
    assert a3[0].token_ids == o2[0].token_ids
    od = oracle.generate([p_div], temperature=0.0, max_new_tokens=16,
                         session_ids=["z"])
    assert d2[0].token_ids == od[0].token_ids


def test_dropped_session_does_not_resurrect_from_host_tier():
    eng = make_engine()
    tier = eng.attach_tier(host_mb=64)
    p1 = enc(SYS + " ephemeral")
    eng.generate([p1], temperature=0.0, max_new_tokens=8,
                 session_ids=["s"])
    hibernate_all(eng)
    assert tier.has_session("s")
    eng.drop_session("s")
    assert not tier.has_session("s")
    assert eng.session_tokens("s") is None


# ---------------------------------------------------------------------------
# Disk prefix store: kill-and-restart warm start, checksum rejection
# ---------------------------------------------------------------------------

def test_disk_store_warm_starts_restarted_process(tmp_path):
    d = str(tmp_path / "kv")
    p1 = enc(SYS + " task one")
    # "process 1": serve traffic; store-back persists prefix blocks
    e1 = make_engine()
    t1 = e1.attach_tier(host_mb=64, disk_dir=d)
    r1 = e1.generate([p1], temperature=0.0, max_new_tokens=16,
                     session_ids=["a"])
    t1.flush_spills()          # disk writes are async (spill queue)
    files = glob.glob(os.path.join(d, "*", "*.npz"))
    assert files, "store-back persisted no prefix blocks"
    # oracle: tierless fresh engine
    rc = make_engine().generate([p1], temperature=0.0, max_new_tokens=16,
                                session_ids=["x"])
    # "process 2" (restart): brand-new engine + store, same disk dir
    e2 = make_engine()
    t2 = e2.attach_tier(host_mb=64, disk_dir=d)
    r2 = e2.generate([p1], temperature=0.0, max_new_tokens=16,
                     session_ids=["b"])
    assert r2[0].token_ids == rc[0].token_ids == r1[0].token_ids
    assert t2.restored_prefix_pages > 0, "no disk warm-start happened"
    assert r2[0].n_cached_tokens >= e2.sessions.page, \
        "restart prompt was not served from the warmed prefix cache"


def test_disk_store_corruption_under_concurrent_readers(tmp_path):
    """ISSUE 11 satellite: an entry corrupted while readers are
    mid-load must skip-unlink-degrade on every path — concurrent
    loaders never crash, never return poisoned KV (crc32 boundary),
    the file unlinks, and a warm-starting engine over the damaged
    store still serves BIT-IDENTICAL outputs by re-prefilling."""
    import threading

    d = str(tmp_path / "kv")
    p1 = enc(SYS + " concurrency victim")
    e1 = make_engine()
    t1 = e1.attach_tier(host_mb=64, disk_dir=d)
    r1 = e1.generate([p1], temperature=0.0, max_new_tokens=16,
                     session_ids=["a"])
    t1.flush_spills()
    files = glob.glob(os.path.join(d, "*", "*.npz"))
    assert files
    victim = files[0]
    key = os.path.basename(victim)[:-len(".npz")]
    store = t1.disk
    blk_tokens = None
    # recover the prefix the victim block stores: page-aligned prefixes
    # of the prompt, matched by content key
    for end in range(e1.sessions.page, len(p1) + 1, e1.sessions.page):
        if DiskPrefixStore.block_key([int(t) for t in p1[:end]]) == key:
            blk_tokens = [int(t) for t in p1[:end]]
            break
    assert blk_tokens is not None

    good = store.load(key, blk_tokens)
    assert good is not None               # sane before corruption

    # corrupt the payload in place, then hammer it from N readers at
    # once: every loader must see either None (corrupt path) — never
    # an exception, never wrong bytes
    with open(victim, "r+b") as f:
        f.seek(os.path.getsize(victim) // 2)
        f.write(b"\xde\xad\xbe\xef" * 8)
    barrier = threading.Barrier(4)
    outcomes: list = []
    errors: list = []

    def reader():
        barrier.wait()
        try:
            outcomes.append(store.load(key, blk_tokens))
        except Exception as exc:          # noqa: BLE001 — the assertion
            errors.append(exc)

    threads = [threading.Thread(target=reader) for _ in range(4)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=30)
    assert not errors, errors
    assert all(o is None for o in outcomes), \
        "a reader returned KV from a corrupted entry"
    assert store.corrupt >= 1
    assert not os.path.exists(victim), "corrupt entry was not unlinked"

    # degrade end-to-end: a fresh engine warm-starting over the
    # damaged store re-prefills and serves identical bits
    oracle = make_engine().generate([p1], temperature=0.0,
                                    max_new_tokens=16, session_ids=["x"])
    e2 = make_engine()
    e2.attach_tier(host_mb=64, disk_dir=d)
    r2 = e2.generate([p1], temperature=0.0, max_new_tokens=16,
                     session_ids=["b"])
    assert r2[0].token_ids == oracle[0].token_ids == r1[0].token_ids


def test_disk_store_skips_and_unlinks_corrupt_entries(tmp_path):
    d = str(tmp_path / "kv")
    p1 = enc(SYS + " task one")
    e1 = make_engine()
    t1 = e1.attach_tier(host_mb=64, disk_dir=d)
    e1.generate([p1], temperature=0.0, max_new_tokens=16,
                session_ids=["a"])
    t1.flush_spills()
    files = glob.glob(os.path.join(d, "*", "*.npz"))
    assert files
    victim = files[0]
    with open(victim, "r+b") as f:
        f.seek(100)
        f.write(b"\x00" * 64)
    rc = make_engine().generate([p1], temperature=0.0, max_new_tokens=16,
                                session_ids=["x"])
    e3 = make_engine()
    t3 = e3.attach_tier(host_mb=64, disk_dir=d)
    r3 = e3.generate([p1], temperature=0.0, max_new_tokens=16,
                     session_ids=["c"])
    # corrupt entry rejected, never served — output matches the oracle
    # via plain prefill, and the bad file was unlinked (the store-back
    # then re-persists a CLEAN block under the same content key)
    assert r3[0].token_ids == rc[0].token_ids
    assert t3.disk.corrupt >= 1
    assert t3.restored_prefix_pages == 0
    t3.flush_spills()          # the clean re-persist is async too
    fresh = DiskPrefixStore(d, os.path.basename(os.path.dirname(victim)))
    key = os.path.splitext(os.path.basename(victim))[0]
    if fresh.has(key):
        # the rewrite is clean: it loads (or it was unlinked entirely)
        toks = None
        with np.load(victim) as z:
            toks = z["tokens"].tolist()
        assert fresh.load(key, toks) is not None


def test_disk_store_round_trips_bfloat16(tmp_path):
    """Serving caches are bfloat16; npz round-trips extension dtypes as
    an opaque void dtype unless the store ships raw bytes + dtype name —
    regression for the silent-dtype-strip the CLI drive caught."""
    s = DiskPrefixStore(str(tmp_path), "sig", model="m")
    toks = list(range(128))
    k = (np.arange(2 * 128 * 2 * 16, dtype=np.float32)
         .reshape(2, 128, 2, 16).astype(jnp.bfloat16))
    v = (k * 2).astype(jnp.bfloat16)
    key = s.block_key(toks)
    assert s.save(key, toks, np.asarray(k), np.asarray(v))
    loaded = s.load(key, toks)
    assert loaded is not None
    lk, lv = loaded
    assert lk.dtype == jnp.bfloat16 and lv.dtype == jnp.bfloat16
    assert lk.tobytes() == np.asarray(k).tobytes()
    assert lv.tobytes() == np.asarray(v).tobytes()


def test_disk_store_rejects_token_mismatch(tmp_path):
    s = DiskPrefixStore(str(tmp_path), "sig", model="m")
    toks = list(range(128))
    k = np.ones((2, 128, 2, 16), np.float32)
    key = s.block_key(toks)
    assert s.save(key, toks, k, k * 2)
    assert s.load(key, toks) is not None
    # same key requested under different tokens (hash collision stand-in)
    # must be rejected, not served
    assert s.load(key, list(range(1, 129))) is None
    assert s.corrupt == 1


def test_disk_store_budget_prunes_oldest_and_touches_on_load(tmp_path):
    """REVIEW fix: the store is byte-bounded — a save that overflows the
    budget prunes oldest-mtime entries, and load() touches mtime so the
    order approximates LRU, not FIFO."""
    kk = np.ones((2, 16, 2, 8), np.float32)

    def toks(i):
        return [i * 1000 + j for j in range(16)]

    s = DiskPrefixStore(str(tmp_path), "sig", model="m")
    keys = []
    for i in range(6):
        key = s.block_key(toks(i))
        keys.append(key)
        assert s.save(key, toks(i), kk, kk)
        os.utime(s._path(key), (1_000_000 + i, 1_000_000 + i))
    per = os.path.getsize(s._path(keys[0]))
    s.budget_bytes = 3 * per + per // 2
    # loading key 0 touches it — despite the oldest write stamp it must
    # survive the prune below
    assert s.load(keys[0], toks(0)) is not None
    assert os.stat(s._path(keys[0])).st_mtime > 1_000_000 + 5
    key6 = s.block_key(toks(6))
    assert s.save(key6, toks(6), kk, kk)      # overflows -> prune
    assert s.pruned >= 1
    assert s.stats()["bytes"] <= s.budget_bytes
    assert s.has(keys[0]) and s.has(key6)     # touched + newest survive
    assert not s.has(keys[1])                 # coldest entry pruned
    # stats serves the incrementally-tracked size, not a fresh listdir
    st = s.stats()
    assert st["entries"] == sum(
        1 for f in os.listdir(s.dir) if f.endswith(".npz"))
    assert st["budget_bytes"] == s.budget_bytes


# ---------------------------------------------------------------------------
# extend_prefix refcount + poisoning regressions (REVIEW fixes)
# ---------------------------------------------------------------------------

def test_restored_prefix_pages_are_evictable(tmp_path):
    """REVIEW fix: a disk/host-restored prefix block must end up with
    the TREE as its only reference holder (like a store-back block after
    its session drops) — the old code kept alloc's base ref and pinned
    every restored page at refcount 2 forever."""
    d = str(tmp_path / "kv")
    p1 = enc(SYS + " task one")
    e1 = make_engine()
    t1 = e1.attach_tier(host_mb=64, disk_dir=d)
    e1.generate([p1], temperature=0.0, max_new_tokens=16,
                session_ids=["a"])
    t1.flush_spills()
    e2 = make_engine()
    t2 = e2.attach_tier(host_mb=64, disk_dir=d)
    e2.generate([p1], temperature=0.0, max_new_tokens=16,
                session_ids=["b"])
    assert t2.restored_prefix_pages > 0
    e2.drop_session("b")
    st = e2.sessions
    with st.lock:
        cached = list(st.prefix_cache._pages)
        assert cached
        for pg in cached:
            assert st._refs.get(pg, 1) == 1, \
                f"page {pg} pinned at refcount {st._refs.get(pg, 1)}"
        # and the tier ladder can actually reclaim them all
        freed = st.prefix_cache.evict(len(cached))
    assert freed == len(cached)


def test_extend_prefix_survives_alloc_evicting_matched_path():
    """REVIEW fix: st.alloc inside extend_prefix can strip the deepest
    node of the just-matched path (leaf-first eviction, and match_len
    bumps no LRU stamps). The restored block must never be inserted
    under the shorter re-walked path — that would label block j's KV
    with block j-1's tokens and serve wrong bytes at temp 0."""
    import jax.numpy as jnp

    from quoracle_tpu.serving.kvtier import _HostBlock
    page = 4
    store = SessionStore(max_tokens=4 * page, page=page)
    L, KV, HD = 2, 2, 4
    # the pool as the engine stores it: [L, n_pages, page, KV·HD]
    store.k = jnp.zeros((L, store.n_pages, page, KV * HD), jnp.float32)
    store.v = jnp.zeros_like(store.k)
    tier = TierManager(store, model="m", host_mb=1)
    store.tier = tier
    tokens = list(range(2 * page))

    def blk(depth, shape=(L, page, KV * HD)):
        return np.full(shape, float(depth), np.float32)

    # both blocks of the chain live in the host tier, content = depth —
    # the first as an entry persisted before the pool was stored
    # lane-flat holds it ([L, page, KV, HD]: the same bytes, restored
    # through a view)
    old = (L, page, KV, HD)
    tier.host.put_prefix(tier._block_key(tokens[:page]),
                         _HostBlock(tokens[:page], blk(1, old), blk(1, old)))
    tier.host.put_prefix(tier._block_key(tokens),
                         _HostBlock(tokens, blk(2), blk(2)))
    # seed the tree with block 0 as a refcount-1 leaf (tree-only ref)
    with store.lock:
        seed = store.alloc(1)
        store.k = store.k.at[:, seed[0]].set(1.0)
        assert store.prefix_cache.insert(tokens[:page], seed) == 1
        store._release(seed)            # tree keeps the only ref
        # hog the remaining free pages so the extend's alloc(1) must
        # evict — and the only evictable page is the matched leaf
        hog = store.alloc(len(store._free))
        assert hog
        tier.extend_prefix(tokens, len(tokens) + 1)
        # a pool this tight cannot hold the whole chain — that is fine;
        # what must NEVER happen is a node whose page holds another
        # depth's KV. The pre-fix code inserted the depth-2 block under
        # the depth-1 label after alloc stripped the matched leaf.
        depth_of = {}
        stack = [(store.prefix_cache._root, 0)]
        while stack:
            node, depth = stack.pop()
            for ch in node.children.values():
                depth_of[ch.page] = depth + 1
                stack.append((ch, depth + 1))
        for pg, depth in depth_of.items():
            got = np.asarray(jax.device_get(store.k[:, pg]))
            assert np.all(got == float(depth)), \
                f"page {pg} at depth {depth} holds wrong KV"
        # and page accounting stayed exact through the shrink/retry
        # dance: every usable page is free, cached, or hogged
        assert (len(store._free) + len(store.prefix_cache._pages)
                + len(hog)) == store.n_pages - 1
        store._release(hog)


# ---------------------------------------------------------------------------
# Host budget + disk spill
# ---------------------------------------------------------------------------

def test_host_budget_evicts_lru_and_spills_prefixes(tmp_path):
    store = SessionStore(max_tokens=8 * 4, page=4)
    tier = TierManager(store, model="m", host_mb=1,
                       disk_dir=str(tmp_path))
    store.tier = tier
    # budget of ~2 tiny blocks: force LRU churn
    blk = np.zeros((2, 4, 2, 4), np.float32)
    tier.host.budget_bytes = 3 * (2 * blk.nbytes)
    from quoracle_tpu.serving.kvtier import _HostBlock
    keys = []
    for i in range(5):
        toks = [100 * i + j for j in range(4)]
        key = tier._block_key(toks)
        keys.append(key)
        tier.host.put_prefix(key, _HostBlock(toks, blk + i, blk + i),
                             spill_fn=tier._spill_prefix_entry)
    assert tier.host.bytes <= tier.host.budget_bytes
    assert tier.host.evicted_prefixes == 2
    # evicted blocks landed on disk, checksummed (async writer)
    tier.flush_spills()
    for key in keys[:2]:
        assert tier.disk.has(key)
    for key in keys[2:]:
        assert key in tier.host.prefixes


def test_host_budget_drops_lru_sessions():
    store = SessionStore(max_tokens=8 * 4, page=4)
    tier = TierManager(store, model="m", host_mb=1)
    store.tier = tier
    from quoracle_tpu.serving.kvtier import _HostSession
    arr = np.zeros((2, 1, 4, 2, 4), np.float32)
    tier.host.budget_bytes = 2 * (2 * arr.nbytes)
    for i in range(4):
        tier.host.put_session(f"s{i}", _HostSession([i], 0, arr.copy(),
                                                    arr.copy()))
    assert tier.host.evicted_sessions == 2
    assert set(tier.host.sessions) == {"s2", "s3"}


# ---------------------------------------------------------------------------
# Prefetch hooks
# ---------------------------------------------------------------------------

def test_prefetch_restores_hibernated_session():
    eng = make_engine()
    tier = eng.attach_tier(host_mb=64)
    p1 = enc(SYS + " warm me")
    eng.generate([p1], temperature=0.0, max_new_tokens=8,
                 session_ids=["s"])
    hibernate_all(eng)
    assert eng.sessions.get("s") is None
    assert eng.prefetch_session("s") is True
    assert eng.sessions.get("s") is not None
    assert tier.restored_sessions == 1
    # idempotent: already-resident session is not restored twice
    assert eng.prefetch_session("s") is False


def test_prefetch_skips_busy_engine():
    eng = make_engine()
    eng.attach_tier(host_mb=64)
    p1 = enc(SYS + " busy case")
    eng.generate([p1], temperature=0.0, max_new_tokens=8,
                 session_ids=["s"])
    hibernate_all(eng)
    with eng._paged_lock:          # simulate an in-flight paged call
        assert eng.prefetch_session("s") is False
    assert eng.prefetch_session("s") is True


def test_continuous_batcher_submit_prefetches():
    from quoracle_tpu.models.scheduler import ContinuousBatcher
    eng = make_engine()
    tier = eng.attach_tier(host_mb=64)
    p1 = enc(SYS + " via scheduler")
    ctl = make_engine()
    o1 = ctl.generate([p1], temperature=0.0, max_new_tokens=8,
                      session_ids=["s"])
    eng.generate([p1], temperature=0.0, max_new_tokens=8,
                 session_ids=["s"])
    hibernate_all(eng)
    cb = ContinuousBatcher(eng, chunk=8, max_slots=2)
    try:
        tok = ByteTokenizer()
        p2 = p1 + o1[0].token_ids + tok.encode(" go on")
        o2 = ctl.generate([p2], temperature=0.0, max_new_tokens=8,
                          session_ids=["s"])
        fut = cb.submit(p2, temperature=0.0, max_new_tokens=8,
                        session_id="s")
        got = fut.result(timeout=120)
        assert got.token_ids == o2[0].token_ids
        assert tier.restored_sessions == 1
    finally:
        cb.close()


def test_backend_prefetch_sessions():
    from quoracle_tpu.models.runtime import TPUBackend
    backend = TPUBackend(pool=["xla:tiny"], host_kv_mb=64)
    assert backend.kv_tiered
    eng = backend.engines["xla:tiny"]
    p1 = enc(SYS + " backend warm")
    eng.generate([p1], temperature=0.0, max_new_tokens=8,
                 session_ids=["agent-1"])
    hibernate_all(eng)
    assert backend.prefetch_sessions("agent-1") == 1
    assert eng.sessions.get("agent-1") is not None
    assert backend.prefetch_sessions("agent-1") == 0
    backend.close()


# ---------------------------------------------------------------------------
# QoS headroom: demotable pages count as reclaimable
# ---------------------------------------------------------------------------

def test_effective_headroom_counts_demotable_pages(monkeypatch):
    from quoracle_tpu.infra import resources
    from quoracle_tpu.models.runtime import TPUBackend
    backend = TPUBackend(pool=["xla:tiny"], host_kv_mb=64)
    eng = backend.engines["xla:tiny"]
    eng.generate([enc(SYS + " hold pages")], temperature=0.0,
                 max_new_tokens=8, session_ids=["s"])
    assert resources.reclaimable_kv_bytes(backend) > 0
    # fake a limit-reporting device so the fraction math is exercised
    monkeypatch.setattr(
        resources, "device_memory_stats",
        lambda: [{"device": 0, "bytes_in_use": 90, "bytes_limit": 100,
                  "peak_bytes_in_use": 0, "platform": "cpu",
                  "kind": "fake", "source": "test"}])
    frac = resources.effective_headroom_fraction(backend)
    assert frac is not None and frac > 0.1   # raw 0.1 + reclaimable
    # untiered backend: effective == raw
    untiered = TPUBackend(pool=["xla:tiny"], engines={"xla:tiny": eng})
    untiered_eng_tier, eng.sessions.tier = eng.sessions.tier, None
    try:
        assert resources.reclaimable_kv_bytes(untiered) == 0
        assert abs(resources.effective_headroom_fraction(untiered)
                   - 0.1) < 1e-9
    finally:
        eng.sessions.tier = untiered_eng_tier
    backend.close()
    untiered.close()


def test_demotable_bytes_excludes_unreclaimable_pages():
    """REVIEW fix: the QoS headroom signal counts only pages the
    eviction ladder could actually free — victim-exclusive session
    pages plus strippable cache leaves. A page pinned by an in-flight
    adopter reference (acquire() without a registered session) is not
    reclaimable and must not be advertised as headroom."""
    eng = make_engine()
    tier = eng.attach_tier(host_mb=64)
    st = eng.sessions
    assert tier.demotable_bytes(1) == 0          # empty store
    eng.generate([enc(SYS + " hold pages")], temperature=0.0,
                 max_new_tokens=8, session_ids=["s"])
    with st.lock:
        base = st._attainable(list(st._sessions)) - len(st._free)
    assert 0 < base <= st.n_pages - 1 - st.free_pages()
    assert tier.demotable_bytes(1) == base
    pinned = [p for p in st.get("s").pages if p][0]
    st.acquire([pinned])                          # in-flight reader
    try:
        assert tier.demotable_bytes(1) == base - 1
    finally:
        st.release([pinned])
    assert tier.demotable_bytes(1) == base
    # still bounded by the remaining host budget
    tier.host.budget_bytes = tier.host.bytes      # zero headroom
    assert tier.demotable_bytes(1) == 0


# ---------------------------------------------------------------------------
# Satellite: the alloc drift branch is loud now
# ---------------------------------------------------------------------------

def test_alloc_drift_counts_and_flight_records(monkeypatch):
    from quoracle_tpu.infra.flightrec import FLIGHT
    from quoracle_tpu.infra.telemetry import KV_ALLOC_DRIFT_TOTAL
    store = SessionStore(max_tokens=4 * 4, page=4)
    store.model = "drifty"
    pages = store.alloc(2)
    store.put("a", _Session(tokens=list(range(8)), pages=pages))
    # force drift: attainability promises pages eviction can't deliver
    monkeypatch.setattr(store, "_attainable", lambda victims: 99)
    before = KV_ALLOC_DRIFT_TOTAL.value(model="drifty")
    assert store.alloc(10) is None
    assert KV_ALLOC_DRIFT_TOTAL.value(model="drifty") == before + 1
    events = [e for e in FLIGHT.snapshot()
              if e.get("kind") == "kv_alloc_drift"
              and e.get("model") == "drifty"]
    assert events and events[-1]["requested"] == 10


# ---------------------------------------------------------------------------
# Satellite: pool_sizing per-tier capacity
# ---------------------------------------------------------------------------

def test_pool_sizing_reports_tier_capacity():
    from quoracle_tpu.parallel.mesh import pool_sizing
    from quoracle_tpu.models.config import NORTH_STAR_POOL
    sizing = pool_sizing(NORTH_STAR_POOL, 8, host_kv_mb=4096,
                         disk_kv_gb=64.0)
    for m in sizing["members"]:
        tiers = m["tiers"]
        assert tiers["hbm_tokens"] == m["resident_kv_tokens"]
        assert tiers["hbm_pages"] == m["resident_kv_tokens"] // 128
        assert tiers["host_kv_mb"] == 4096
        assert tiers["host_kv_tokens"] > 0
        assert tiers["disk_kv_tokens"] > tiers["host_kv_tokens"]
    assert sizing["host_kv_mb_per_member"] == 4096
    # host tier capacity uses UNSHARDED bytes/token: it must not exceed
    # what the budget divided by the tp=1 rate allows
    from quoracle_tpu.models.config import get_model_config
    for m in sizing["members"]:
        cfg = get_model_config(f"xla:{m['model']}") \
            if not m["model"].startswith("xla:") else \
            get_model_config(m["model"])
        rate = cfg.kv_bytes_per_token(1, 2)
        assert m["tiers"]["host_kv_tokens"] == (4096 << 20) // rate
    # omitting the knobs keeps the tier block zeroed, not absent
    plain = pool_sizing(NORTH_STAR_POOL, 8)
    assert plain["members"][0]["tiers"]["host_kv_tokens"] == 0


# ---------------------------------------------------------------------------
# API + exposition
# ---------------------------------------------------------------------------

def test_kv_stats_and_prometheus_exposition():
    from quoracle_tpu.infra.telemetry import METRICS
    from quoracle_tpu.models.runtime import TPUBackend
    backend = TPUBackend(pool=["xla:tiny"], host_kv_mb=64)
    eng = backend.engines["xla:tiny"]
    eng.generate([enc(SYS + " stats")], temperature=0.0,
                 max_new_tokens=8, session_ids=["s"])
    hibernate_all(eng)
    eng.generate([enc(SYS + " stats")], temperature=0.0,
                 max_new_tokens=8, session_ids=["s"])
    stats = backend.kv_stats()
    assert stats["enabled"]
    m = stats["members"]["xla:tiny"]
    assert m["demoted_sessions"] >= 1
    assert m["restored_sessions"] >= 1
    assert m["hbm"]["pages"] == eng.sessions.n_pages
    text = METRICS.render_prometheus()
    assert "quoracle_kv_demotes_total" in text
    assert "quoracle_kv_restores_total" in text
    assert "quoracle_kv_restore_ms" in text
    assert 'kind="session"' in text
    backend.close()


def test_api_kv_payload_shapes():
    """kv_payload over a MockBackend (no tiering) and the TPU backend —
    the endpoint must answer in both worlds."""
    from quoracle_tpu.models.runtime import MockBackend, TPUBackend

    class _FakeRuntime:
        def __init__(self, backend):
            self.backend = backend

    from quoracle_tpu.web.server import DashboardServer
    d = DashboardServer.__new__(DashboardServer)
    d.runtime = _FakeRuntime(MockBackend())
    payload = d.kv_payload()
    assert payload["enabled"] is False
    assert "counters" in payload

    backend = TPUBackend(pool=["xla:tiny"], host_kv_mb=64)
    d.runtime = _FakeRuntime(backend)
    payload = d.kv_payload()
    assert payload["enabled"] is True
    assert "xla:tiny" in payload["members"]
    backend.close()


def test_kv_panel_renders():
    from quoracle_tpu.web.views import kv_panel
    assert kv_panel({"enabled": False}) == ""
    html = kv_panel({"enabled": True, "members": {"xla:tiny": {
        "hbm": {"pages": 10, "free_pages": 4, "used_pages": 5,
                "sessions": 2, "prefix_cache": {}},
        "host": {"bytes": 1 << 20, "budget_bytes": 64 << 20,
                 "sessions": 3, "prefix_blocks": 7},
        "disk": {"entries": 11, "corrupt_skipped": 0},
        "demoted_sessions": 5, "restored_sessions": 4,
    }}})
    assert "tiered KV" in html and "xla:tiny" in html and "11" in html


# ---------------------------------------------------------------------------
# Flight-recorder events
# ---------------------------------------------------------------------------

def test_demote_restore_flight_events():
    from quoracle_tpu.infra.flightrec import FLIGHT
    eng = make_engine()
    eng.attach_tier(host_mb=64)
    p1 = enc(SYS + " flight")
    eng.generate([p1], temperature=0.0, max_new_tokens=8,
                 session_ids=["s"])
    hibernate_all(eng)
    eng.generate([p1], temperature=0.0, max_new_tokens=8,
                 session_ids=["s"])
    kinds = [e["kind"] for e in FLIGHT.snapshot()]
    assert "kv_demote" in kinds
    assert "kv_restore" in kinds


@pytest.mark.parametrize("layout", ["stored", "kv-hd"])
def test_page_in_takes_host_pages_in_either_layout(layout):
    """The pool is stored [L, n_pages, page, KV·HD] (generate.py
    _ensure_pool) and the host tiers hold pages the same way; an entry
    persisted before that holds the same bytes as [L, n, page, KV, HD]
    and pages in through a view — the padded page count (3 → 4, the
    spare slot on scratch page 0) included."""
    import jax.numpy as jnp
    page, L, KV, HD = 4, 2, 2, 4
    store = SessionStore(max_tokens=6 * page, page=page)
    store.k = jnp.zeros((L, store.n_pages, page, KV * HD), jnp.float32)
    store.v = jnp.zeros_like(store.k)
    tier = TierManager(store, model="m", host_mb=1)
    rng = np.random.default_rng(0)
    k, v = (rng.standard_normal((L, 3, page, KV, HD)).astype(np.float32)
            for _ in range(2))
    flat = (L, 3, page, KV * HD)
    host = (k, v) if layout == "kv-hd" else (k.reshape(flat),
                                             v.reshape(flat))
    tier._scatter_device([5, 2, 6], *host)
    for pool, want in ((store.k, k), (store.v, v)):
        got = np.asarray(pool)
        assert np.array_equal(got[:, [5, 2, 6]], want.reshape(flat))
        assert not got[:, [1, 3, 4]].any()
    # and what a demotion reads back is the stored layout
    back = tier._gather_host([2, 6])
    assert back[0].shape == (L, 2, page, KV * HD)
    assert np.array_equal(back[0], k.reshape(flat)[:, 1:])
