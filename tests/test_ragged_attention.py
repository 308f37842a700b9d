"""Unified ragged serving path (ISSUE 8, models/generate.py
_run_unified): one token-major launch per layer for the whole mixed tick —
prefill suffixes, continuations, decode steps and speculative-verify
windows — with KV written straight to pages. The kernels themselves are
held to their oracles a file each (tests/test_ragged_tile_kernel.py,
_shared_walk, _block_walk, _walk_ahead, _host_tables, _pool_in_place;
helpers in tests/_ragged_cases.py). Here the ENGINE:

  * temp-0 BIT-EQUALITY of the unified path vs the gather path for
    greedy, grammar-constrained, and speculative-verify decodes — the
    same bar every serving layer in this repo holds;
  * the rule by which a tick takes the ragged programs or falls back;
  * the compile-count COLLAPSE: a 50-tick mixed-shape run through the
    unified path lands on ≤ RAGGED_PROGRAM_BOUND CompileRegistry keys
    (one (chunk, decode) program pair per (token-budget, table-width)
    bucket), strictly fewer than the bucketed gather baseline compiles
    for the identical traffic.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from quoracle_tpu.models.config import get_model_config
from quoracle_tpu.models.generate import PAGE, GenerateEngine
from quoracle_tpu.models.tokenizer import ByteTokenizer
from quoracle_tpu.models.transformer import init_params
from quoracle_tpu.ops import paged_attention as pa
from tests._ragged_cases import _gather, enc, make_engine

# Documented program-count bound for the 50-tick mixed-shape traffic in
# test_compile_collapse_vs_bucketed_baseline (ARCHITECTURE.md §10): each
# CompileRegistry key is one ("ragged", token-budget bucket, table width,
# decode bound) tuple = one chunk + one decode program. The traffic below
# spans ≤ 4 token-budget buckets × ≤ 2 table widths.
RAGGED_PROGRAM_BOUND = 8


# --- engine equality: unified vs gather -------------------------------------


def test_unified_matches_gather_greedy():
    """Temp-0 bit-equality for a mixed batch (sessioned + sessionless
    rows) across a fresh call and a resumed refinement round."""
    def run(eng):
        pa = enc("user: compare decode paths please")
        pb = enc("user: a sessionless neighbor row")
        r = eng.generate([pa, pb], temperature=0.0, max_new_tokens=10,
                         session_ids=["s", None])
        pa2 = pa + r[0].token_ids + enc(" go on")[1:]
        r2 = eng.generate([pa2, pb], temperature=0.0, max_new_tokens=10,
                          session_ids=["s", None])
        return [x.token_ids for x in r + r2]

    got, want = run(make_engine()), run(_gather(make_engine()))
    assert got == want


def test_unified_matches_gather_constrained_json():
    """Grammar-constrained decode (action-enum JSON) through the unified
    kernel must be token- AND state-identical to the gather path."""
    def run(eng):
        p1 = enc("user: emit an action")
        p2 = enc("user: second row same grammar")
        r = eng.generate([p1, p2], temperature=0.0, max_new_tokens=20,
                         session_ids=["a", "b"],
                         constrain_json=[True, True],
                         action_enums=[("walk", "talk"), ("walk", "talk")])
        return [(x.token_ids, x.json_state) for x in r]

    got, want = run(make_engine()), run(_gather(make_engine()))
    assert got == want


def test_unified_matches_gather_speculative_verify():
    """verify_chunk — the speculative target side — through the unified
    kernel: identical verdict ids, probs, and cached-token counts."""
    def run(eng, need_probs):
        p = enc("user: verify me please with some context")
        r = eng.generate([p], temperature=0.0, max_new_tokens=6,
                         session_ids=["v"])[0]
        ctx = p + r.token_ids
        props = [5, 6, 7, 8]
        out = eng.verify_chunk([ctx + props], ["v"], [4],
                               need_probs=need_probs)[0]
        return r.token_ids, out["ids"], out["n_cached"], out["probs"]

    for need_probs in (False, True):
        t1, v1, c1, p1 = run(make_engine(), need_probs)
        t2, v2, c2, p2 = run(_gather(make_engine()), need_probs)
        assert (t1, v1, c1) == (t2, v2, c2)
        if need_probs:
            np.testing.assert_array_equal(p1, p2)   # one-hot at temp 0


def test_unified_matches_gather_constrained_verify():
    """Constrained verify: the in-device grammar walk over the window must
    apply the same masks on both paths (bit-equal verdicts)."""
    def run(eng):
        p = enc("user: act")
        r = eng.generate([p], temperature=0.0, max_new_tokens=8,
                         session_ids=["cv"], constrain_json=[True],
                         action_enums=[("walk", "talk")])[0]
        ctx = p + r.token_ids
        props = enc('{"a')[1:][:3]
        out = eng.verify_chunk([ctx + props], ["cv"], [3],
                               constrain_json=[True],
                               action_enums=[("walk", "talk")],
                               initial_json_state=[r.json_state])[0]
        return r.token_ids, out["ids"]

    assert run(make_engine()) == run(_gather(make_engine()))


def test_unified_windowed_resume_matches_fresh():
    """Sliding-window model through the unified kernel: a trimmed-session
    resume (nonzero kv position offset) must match a fresh full prefill
    — the window mask is buffer-relative inside the kernel."""
    import tests.test_paged_kv  # noqa: F401 — registers xla:tiny-window
    cfg = get_model_config("xla:tiny-window")
    params = init_params(cfg, jax.random.PRNGKey(1), dtype=jnp.float32)
    cached = GenerateEngine(cfg, params, ByteTokenizer(), max_seq=1024,
                            prompt_buckets=(64, 128, 256, 512))
    fresh = GenerateEngine(cfg, params, ByteTokenizer(), max_seq=1024,
                           prompt_buckets=(64, 128, 256, 512))
    p = enc("u: " + "window test " * 30)
    r1 = cached.generate([p], temperature=0.0, max_new_tokens=8,
                         session_ids=["w"])[0]
    assert cached.sessions.get("w").start_pos > 0
    p2 = p + r1.token_ids + enc(" continue")[1:]
    want = fresh.generate([p2], temperature=0.0, max_new_tokens=8)[0]
    got = cached.generate([p2], temperature=0.0, max_new_tokens=8,
                          session_ids=["w"])[0]
    assert got.token_ids == want.token_ids
    assert got.n_cached_tokens > 0


def test_unified_releases_temp_pages():
    """Sessionless rows borrow pool pages for the unified tick; every
    page must come back after the call."""
    eng = make_engine()
    p = enc("user: temp page bookkeeping")
    eng.generate([p], temperature=0.0, max_new_tokens=6,
                 session_ids=["a"])
    free0 = eng.sessions.free_pages()
    p2 = enc("user: another prompt entirely")
    eng.generate([p, p2], temperature=0.0, max_new_tokens=6,
                 session_ids=["a", None])
    assert eng.sessions.free_pages() == free0


# --- the rule of the paged path (generate.ragged_fallback) -------------------


def _decode_paths(eng) -> list:
    """Record which paged decode program each of ``eng``'s ticks runs."""
    ran = []
    for name in ("_step_paged_decode_ragged", "_step_paged_decode"):
        def step(*a, _step=getattr(eng, name), _name=name, **kw):
            ran.append("ragged" if _name.endswith("ragged") else "gather")
            return _step(*a, **kw)
        setattr(eng, name, step)
    return ran


def test_every_platform_takes_the_ragged_path(tmp_path, monkeypatch):
    """A fresh dense engine, on whatever platform the tests run on and
    with nothing in its environment, serves sessions through the ragged
    programs: every CompileRegistry key is the ragged program identity.
    The operator-supplied gate file is gone with the choice it made:
    whatever the rehearsal configuration still exports (the variable
    that once named the file) pointed at a file that says OFF changes
    nothing, because nothing reads it."""
    import json
    import os

    def keys():
        eng = make_engine()
        p = enc("user: which path serves me")
        r = eng.generate([p, enc("user: a sessionless neighbour")],
                         temperature=0.0, max_new_tokens=6,
                         session_ids=["s", None])
        eng.generate([p + r[0].token_ids + enc(" and again")[1:]],
                     temperature=0.0, max_new_tokens=6, session_ids=["s"])
        # the snapshot lists the dearest compile first: an order that
        # follows the machine's load and the compile cache, not the path
        return sorted(e["shape"] for e in eng.compiles.snapshot()["shapes"])

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark/configs/tiny-l2.json")) as f:
        exported = json.load(f).get("env", {})
    for var in exported:
        monkeypatch.delenv(var, raising=False)
    bare = keys()
    assert bare and all(k.startswith("ragged") for k in bare), bare
    off = tmp_path / "gates.json"
    off.write_text(json.dumps({
        "decode_min_resident": None, "prefill_min_resident": None,
        "prefill_max_chunk": 1024, "unified_min_resident": None,
        "device_kind": getattr(jax.devices()[0], "device_kind", "")}))
    for var in exported:
        monkeypatch.setenv(var, str(off))
    assert keys() == bare


LONG = dict(max_seq=1024, prompt_buckets=(64, 128, 256, 512))


def _outgrows_the_pool(eng):
    """Tick 2 resumes session "a" with a suffix the pool cannot hold even
    after eviction: the row reuses its prefix, its store is declined."""
    p = enc("user: a session that will outgrow the pool")
    r1 = eng.generate([p], temperature=0.0, max_new_tokens=4,
                      session_ids=["a"])[0]
    big = p + r1.token_ids + enc(" and then " + "x" * 400)[1:]
    r2 = eng.generate([big], temperature=0.0, max_new_tokens=4,
                      session_ids=["a"])[0]
    assert r2.n_cached_tokens > 0
    r3 = eng.generate([p + r1.token_ids + enc(" go on")[1:]],
                      temperature=0.0, max_new_tokens=4,
                      session_ids=["a"])[0]
    return [r1, r2, r3]


def _diverges_inside_a_shared_page(eng):
    """Tick 2 diverges session "a" in the middle of its second page,
    which the radix prefix cache holds too: the boundary page is swapped
    for a fresh one (copy-on-write) with its reused head unwritten."""
    pa = enc("system: " + "policy rules apply here. " * 12
             + "user: task alpha")                      # > 2 pages
    r1 = eng.generate([pa], temperature=0.0, max_new_tokens=8,
                      session_ids=["a"])[0]
    div = pa[:150] + enc("user: a different continuation")[1:]
    r2 = eng.generate([div], temperature=0.0, max_new_tokens=8,
                      session_ids=["a"])[0]
    r3 = eng.generate([div + r2.token_ids + enc(" next")[1:]],
                      temperature=0.0, max_new_tokens=8,
                      session_ids=["a"])[0]
    assert r3.n_cached_tokens >= len(div)    # resumes on the swapped page
    return [r1, r2, r3]


def _neighbour_finds_no_scratch(eng):
    """Tick 2 carries a sessionless row longer than the free list: the
    one reason found only by trying (no page for its temporaries)."""
    p = enc("user: a short resident session")
    r1 = eng.generate([p], temperature=0.0, max_new_tokens=4,
                      session_ids=["a"])[0]
    again = p + r1.token_ids + enc(" go on")[1:]
    r2 = eng.generate([again, enc("x" * 400)], temperature=0.0,
                      max_new_tokens=4, session_ids=["a", None])
    r3 = eng.generate([again + r2[0].token_ids + enc(" more")[1:]],
                      temperature=0.0, max_new_tokens=4,
                      session_ids=["a"])[0]
    return [r1, *r2, r3]


@pytest.mark.parametrize("drive,pool_tokens", [
    (_outgrows_the_pool, 2 * PAGE),
    (_diverges_inside_a_shared_page, None),
    (_neighbour_finds_no_scratch, 2 * PAGE),
], ids=["declined_store", "swapped_boundary_page", "no_temporary_page"])
def test_a_tick_falls_back_for_a_reason_it_can_observe(drive, pool_tokens):
    """Each condition of ``ragged_fallback`` in turn routes ONE tick, the
    second of three, to the gather programs: the tokens are those of an
    engine no condition can arise in (room to spare, no shared pages) on
    the same prompts, which serves all three ticks ragged; the tick after
    is ragged again; and no page leaks."""
    eng, ref = make_engine(**LONG), make_engine(**LONG)
    if pool_tokens:
        eng.sessions.__init__(max_tokens=pool_tokens)
    ref.prefix_sharing = False
    baseline = eng.sessions.free_pages()
    ran, ran_ref = _decode_paths(eng), _decode_paths(ref)
    got, want = drive(eng), drive(ref)
    assert ran == ["ragged", "gather", "ragged"]
    assert ran_ref == ["ragged"] * 3
    assert [r.token_ids for r in got] == [r.token_ids for r in want]
    eng.drop_session("a")
    with eng.sessions.lock:
        eng.sessions.prefix_cache.clear()
    assert eng.sessions.free_pages() == baseline


def test_engine_builds_only_programs_it_can_reach():
    """The step programs of a plain single-device engine are this list:
    three ragged, the four gather programs a tick falls back to
    (``ragged_fallback``), and the dense-cache pair of a sessionless
    call. A further family of paged programs cannot grow back unseen."""
    eng = make_engine()
    built = {k for k, v in vars(eng).items()
             if k.startswith("_step_") and v is not None}
    assert built == {
        "_step_paged_ragged", "_step_paged_decode_ragged",
        "_step_paged_ragged_verify",
        "_step_paged_prefill", "_step_paged_decode", "_step_paged_verify",
        "_step_scatter_prompt",
        "_step_prefill", "_step_decode"}


# --- padding telemetry ------------------------------------------------------


def test_padding_telemetry_quantifies_raggedness():
    """quoracle_sched_{real,padded}_tokens_total: both paths count the
    same real tokens; the unified path's padded slots are bounded by the
    per-row tq round-up (strictly fewer than the [B·T] rectangle for
    ragged traffic)."""
    from quoracle_tpu.infra.telemetry import (
        SCHED_PADDED_TOKENS_TOTAL, SCHED_REAL_TOKENS_TOTAL,
    )
    prompts = [enc("user: short"), enc("user: a much longer neighbor "
                                       "row that pads the bucket " * 3)]

    def run(eng):
        name = eng.cfg.name
        r0 = SCHED_REAL_TOKENS_TOTAL.value(model=name)
        p0 = SCHED_PADDED_TOKENS_TOTAL.value(model=name)
        eng.generate(prompts, temperature=0.0, max_new_tokens=4,
                     session_ids=["x", "y"])
        return (SCHED_REAL_TOKENS_TOTAL.value(model=name) - r0,
                SCHED_PADDED_TOKENS_TOTAL.value(model=name) - p0)

    real_u, padded_u = run(make_engine())
    real_g, padded_g = run(_gather(make_engine()))
    assert real_u == real_g == sum(len(p) for p in prompts)
    assert padded_u >= real_u and padded_g >= real_g
    assert padded_u < padded_g          # raggedness reclaimed padding
    stats = make_engine().padding_stats()
    assert stats["ticks"] == 0 and stats["waste_ratio"] is None


# --- compile-count collapse --------------------------------------------------


def _mixed_traffic():
    """50 ticks of mixed-shape traffic: batch sizes 1-5, short interactive
    rows next to long agent rows, fresh sessions each tick (dropped after
    — shapes, not capacity, are under test)."""
    base = ("user: tell me a thing",
            "agent: a considerably longer preamble with lots of words "
            "that lands this row in a larger prompt bucket " * 2,
            "user: mid sized request with some extra words",
            "user: tiny",
            "agent: another long row " * 6)
    ticks = []
    for t in range(50):
        nrows = 1 + t % 5
        ticks.append([enc(base[(t + j) % 5] + f" t{t}")
                      for j in range(nrows)])
    return ticks


def test_compile_collapse_vs_bucketed_baseline():
    """The acceptance gate (ISSUE 8): 50 mixed-shape ticks through the
    unified kernel compile ≤ RAGGED_PROGRAM_BOUND CompileRegistry keys —
    and strictly fewer than the bucketed gather baseline compiles for
    identical traffic (batch-bucket × prompt-bucket matrix collapsed to
    token-budget buckets)."""
    ticks = _mixed_traffic()

    def run(eng):
        for t, prompts in enumerate(ticks):
            sids = [f"t{t}-{j}" for j in range(len(prompts))]
            eng.generate(prompts, temperature=0.0, max_new_tokens=4,
                         session_ids=sids)
            for s in sids:
                eng.drop_session(s)
        return eng.compiles

    uni = run(make_engine())
    gat = run(_gather(make_engine()))
    assert uni.misses <= RAGGED_PROGRAM_BOUND, uni.snapshot()
    assert uni.misses < gat.misses, (uni.snapshot(), gat.snapshot())
    # every unified key is the ragged program identity, not a [B, T] shape
    assert all(e["shape"].startswith("ragged")
               for e in uni.snapshot()["shapes"])
