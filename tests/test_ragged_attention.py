"""Unified ragged serving kernel (ISSUE 8, ops/paged_attention.py
ragged_attend / models/generate.py _run_unified): one token-major launch
per layer for the whole mixed tick — prefill suffixes, continuations,
decode steps and speculative-verify windows — with KV written straight to
pages. Tier-1 asserts three things:

  * the Pallas kernel (interpret mode off-TPU) agrees with the dense
    gather oracle across geometries: GQA groupings, page sizes, empty
    (inert) blocks, single-token rows, and rows at the sliding-window
    edge;
  * temp-0 BIT-EQUALITY of the unified path vs the gather path for
    greedy, grammar-constrained, and speculative-verify decodes — the
    same bar every serving layer in this repo holds;
  * the compile-count COLLAPSE: a 50-tick mixed-shape run through the
    unified path lands on ≤ RAGGED_PROGRAM_BOUND CompileRegistry keys
    (one (chunk, decode) program pair per (token-budget, table-width)
    bucket), strictly fewer than the bucketed gather baseline compiles
    for the identical traffic.
"""

import jax
import jax.numpy as jnp
import numpy as np

from quoracle_tpu.models.config import get_model_config
from quoracle_tpu.models.generate import (
    RAGGED_TQ, GenerateEngine,
)
from quoracle_tpu.models.tokenizer import ByteTokenizer
from quoracle_tpu.models.transformer import init_params

# Documented program-count bound for the 50-tick mixed-shape traffic in
# test_compile_collapse_vs_bucketed_baseline (ARCHITECTURE.md §10): each
# CompileRegistry key is one ("ragged", token-budget bucket, table width,
# decode bound) tuple = one chunk + one decode program. The traffic below
# spans ≤ 4 token-budget buckets × ≤ 2 table widths.
RAGGED_PROGRAM_BOUND = 8


def make_engine(name="xla:tiny", seed=0, **kw):
    cfg = get_model_config(name)
    params = init_params(cfg, jax.random.PRNGKey(seed), dtype=jnp.float32)
    return GenerateEngine(cfg, params, ByteTokenizer(),
                          max_seq=kw.pop("max_seq", 256),
                          prompt_buckets=kw.pop("prompt_buckets",
                                                (32, 64, 128)),
                          **kw)


def enc(text):
    return ByteTokenizer().encode(text, add_bos=True)


def _unified(eng):
    eng.unified_min_tokens = 0          # force the unified kernel path
    return eng


def _gather(eng):
    eng._force_gather_decode = True     # the equality/fallback seam
    return eng


# --- kernel vs dense oracle -------------------------------------------------


def _random_case(rng, rows, H, KV, hd, page, n_pages, window):
    """Build a flat layout from (prefix, q_len) rows and run kernel
    (interpret) vs the dense gather oracle."""
    from quoracle_tpu.ops.paged_attention import (
        ragged_attend, ragged_attend_ref,
    )
    tq = RAGGED_TQ
    maxp = max(-(-(pre + q) // page) for pre, q in rows if q > 0)
    NB = sum(-(-q // tq) if q else 1 for pre, q in rows)
    Tp = NB * tq
    q = jnp.asarray(rng.standard_normal((Tp, H, hd)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((n_pages, page, KV, hd)),
                     jnp.float32)
    vp = jnp.asarray(rng.standard_normal((n_pages, page, KV, hd)),
                     jnp.float32)
    rtab = np.zeros((len(rows), maxp), np.int32)
    bmeta = np.zeros((4, NB), np.int32)     # kv_len, qpos0, nq, row
    next_page = 1
    cur_blk = 0
    for r, (pre, qlen) in enumerate(rows):
        nb = -(-qlen // tq) if qlen else 1
        rtab[r] = [(next_page + j) % (n_pages - 1) + 1
                   for j in range(maxp)]
        next_page += maxp
        for b in range(nb):
            bmeta[:, cur_blk + b] = (pre + qlen, pre + b * tq,
                                     max(0, min(tq, qlen - b * tq)), r)
        cur_blk += nb
    ref = ragged_attend_ref(q, kp, vp, jnp.asarray(rtab),
                            jnp.asarray(bmeta), tq=tq,
                            sliding_window=window)
    krn = ragged_attend(q, kp, vp, jnp.asarray(rtab), jnp.asarray(bmeta),
                        tq=tq, sliding_window=window,
                        interpret=jax.devices()[0].platform != "tpu")
    np.testing.assert_allclose(np.asarray(ref), np.asarray(krn),
                               rtol=2e-4, atol=2e-4)
    return np.asarray(krn), bmeta


def test_ragged_kernel_matches_oracle_geometries():
    """Interpret-mode kernel vs the dense oracle: GQA groupings, two page
    sizes, decode (single-token) rows, chunk rows, and empty (inert)
    blocks in one grid."""
    rng = np.random.default_rng(3)
    #       rows: (prefix, q_len); q_len 0 = inert block (padding slot)
    rows = [(40, 1), (17, 11), (0, 19), (5, 0), (63, 1)]
    for H, KV in ((8, 2), (4, 4), (6, 1)):
        for page in (8, 16):
            _random_case(rng, rows, H, KV, 32, page, 24, None)


def test_ragged_kernel_window_edges():
    """Sliding-window masking at the hard spots: window smaller than a
    page, window exactly at a page boundary, query at position 0, and a
    decode token whose window excludes every resident page but its own."""
    rng = np.random.default_rng(4)
    page = 16
    for window in (3, page, page + 1, 24):
        rows = [(0, 9),              # fresh chunk, window inside chunk
                (2 * page, 1),       # decode at a page boundary
                (window, 1),         # window exactly excludes the prefix
                (37, 5)]             # straddles pages mid-way
        _random_case(rng, rows, 8, 2, 32, page, 24, window)


def test_ragged_kernel_empty_and_inert_blocks_are_zero():
    """nq = 0 blocks (padding) must come out exactly zero — no NaNs to
    poison downstream einsums."""
    rng = np.random.default_rng(5)
    out, bmeta = _random_case(rng, [(12, 3), (9, 0)], 8, 2, 32, 16, 12,
                              None)
    tq = RAGGED_TQ
    assert np.all(np.isfinite(out))
    # row 0: queries 3..7 of block 0 are padding; row 1's block is inert
    assert np.all(out[3:tq] == 0.0)
    assert np.all(out[tq:] == 0.0)


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

    got, want = run(_unified(make_engine())), run(_gather(make_engine()))
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

    got, want = run(_unified(make_engine())), run(_gather(make_engine()))
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
        t1, v1, c1, p1 = run(_unified(make_engine()), need_probs)
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

    assert run(_unified(make_engine())) == run(_gather(make_engine()))


def test_unified_windowed_resume_matches_fresh():
    """Sliding-window model through the unified kernel: a trimmed-session
    resume (nonzero kv position offset) must match a fresh full prefill
    — the window mask is buffer-relative inside the kernel."""
    import tests.test_paged_kv  # noqa: F401 — registers xla:tiny-window
    cfg = get_model_config("xla:tiny-window")
    params = init_params(cfg, jax.random.PRNGKey(1), dtype=jnp.float32)
    cached = _unified(GenerateEngine(cfg, params, ByteTokenizer(),
                                     max_seq=1024,
                                     prompt_buckets=(64, 128, 256, 512)))
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
    eng = _unified(make_engine())
    p = enc("user: temp page bookkeeping")
    eng.generate([p], temperature=0.0, max_new_tokens=6,
                 session_ids=["a"])
    free0 = eng.sessions.free_pages()
    p2 = enc("user: another prompt entirely")
    eng.generate([p, p2], temperature=0.0, max_new_tokens=6,
                 session_ids=["a", None])
    assert eng.sessions.free_pages() == free0


# --- calibration gate + padding telemetry -----------------------------------


def test_unified_gate_calibration(tmp_path, monkeypatch):
    """unified_min_resident: explicit value wins, explicit null = off,
    ABSENT key (old files) = auto — off on CPU, so old calibration files
    keep exactly their old behavior here."""
    from quoracle_tpu.utils.calibration import (
        load_paged_gates, resolve_unified_gate, save_paged_gates,
    )
    here = getattr(jax.devices()[0], "device_kind", "")
    explicit = str(tmp_path / "explicit.json")
    save_paged_gates(explicit, decode_min_resident=None,
                     prefill_min_resident=None, unified_min_resident=2048,
                     device_kind=here)
    monkeypatch.setenv("QUORACLE_PAGED_CALIB", explicit)
    g = load_paged_gates()
    assert g.unified_min_resident == 2048
    assert resolve_unified_gate(g) == 2048
    assert make_engine().unified_min_tokens == 2048

    off = str(tmp_path / "off.json")
    save_paged_gates(off, decode_min_resident=None,
                     prefill_min_resident=None, unified_min_resident=None,
                     device_kind=here)
    monkeypatch.setenv("QUORACLE_PAGED_CALIB", off)
    assert load_paged_gates().unified_min_resident == 1 << 30

    legacy = str(tmp_path / "legacy.json")
    save_paged_gates(legacy, decode_min_resident=4096,
                     prefill_min_resident=None, device_kind=here)
    monkeypatch.setenv("QUORACLE_PAGED_CALIB", legacy)
    g = load_paged_gates()
    assert g.unified_min_resident is None          # AUTO
    assert g.decode_min_resident == 4096           # old keys still honored
    on_tpu = jax.devices()[0].platform == "tpu"
    assert resolve_unified_gate(g) == (0 if on_tpu else 1 << 30)


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

    real_u, padded_u = run(_unified(make_engine()))
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

    uni = run(_unified(make_engine()))
    gat = run(_gather(make_engine()))
    assert uni.misses <= RAGGED_PROGRAM_BOUND, uni.snapshot()
    assert uni.misses < gat.misses, (uni.snapshot(), gat.snapshot())
    # every unified key is the ragged program identity, not a [B, T] shape
    assert all(e["shape"].startswith("ragged")
               for e in uni.snapshot()["shapes"])
