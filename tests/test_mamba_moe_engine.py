"""Nemotron-H's toy (tests/test_mamba_moe.py) THROUGH THE ENGINE: the record
a session goes on from, the snapshot a second session finds missing, takes
and a third adopts (a copy: no shared writer), a match cut back to the
deepest snapshot, a record evicted under pressure, the pool's balance, and
each refusal. A file of its own so that the two halves run on two workers
(`--dist loadfile`)."""

import jax.numpy as jnp
import numpy as np
import pytest

from quoracle_tpu.models import transformer as tr
from quoracle_tpu.models.generate import GenerateEngine
from quoracle_tpu.models.tokenizer import ByteTokenizer
from tests.test_mamba_moe import (   # noqa: F401  (toy: a fixture)
    PAGE, RAW, TOL, f32, model, tokens_of, toy,
)


def new_engine(toy):
    cfg, params, _ = toy
    return GenerateEngine(cfg, f32(params), ByteTokenizer(), max_seq=1024,
                          prompt_buckets=(32, 64, 128, 256, 512))


@pytest.fixture()
def engine(toy):
    return new_engine(toy)


def served(eng, ref, prompt, sid, n=6):
    """Serve `prompt` greedily under session `sid`; returns (the result,
    the larger of: how far the chunk forward's logits at the prompt's end
    lie from the reference's, and the widest gap by which a token the
    decode loop then served lies below the reference's best at its
    position)."""
    chunk = eng._step_paged_ragged
    seen = []

    def spy(*a, **kw):
        out = chunk(*a, **kw)
        seen.append(np.asarray(out[0][0]))
        return out

    eng._step_paged_ragged = spy
    try:
        res = eng.generate([list(prompt)], temperature=0.0,
                           max_new_tokens=n, session_ids=[sid])[0]
    finally:
        eng._step_paged_ragged = chunk
    ids = list(prompt) + res.token_ids
    rows = np.arange(len(prompt) - 1, len(ids) - 1)
    want = ref.logits(np.pad(np.asarray(ids, np.int32),
                             (0, 640 - len(ids))), rows)
    gaps = want.max(-1) - want[np.arange(len(rows)), res.token_ids]
    return res, max(float(gaps.max()), float(np.abs(seen[0] - want[0]).max()))


def counts(cfg):
    from quoracle_tpu.infra.telemetry import (
        SSM_STATE_RECORDS_TOTAL, SSM_STATE_REPREFILL_TOKENS_TOTAL,
        SSM_STATE_ROWS_TOTAL,
    )
    return {**{s: SSM_STATE_ROWS_TOTAL.value(model=cfg.name, source=s)
               for s in ("carried", "adopted", "zero")},
            **{k: SSM_STATE_RECORDS_TOTAL.value(model=cfg.name, kind=k)
               for k in ("snapshot", "copy", "evicted")},
            "reprefill": SSM_STATE_REPREFILL_TOKENS_TOTAL.value(
                model=cfg.name)}


def moved(cfg, before):
    return {k: v - before[k] for k, v in counts(cfg).items() if
            v != before[k]}


def held(eng):
    """(records sessions hold, snapshots the cache holds, free records)."""
    st = eng.sessions
    return (sorted(s.record for s in st._sessions.values()),
            st.prefix_cache.stats()["cached_records"],
            len(st.records._free))


def ids_of(seed, n):
    return [int(t) for t in tokens_of(seed, n)]


@pytest.mark.parametrize("n", [200, 256], ids=["inside-a-page", "on-a-page"])
def test_a_session_goes_on_from_its_own_record(engine, toy, n):
    """Turn after turn the session's ONE record is read and written in
    place: nothing of the earlier turns runs again."""
    cfg, _, ref = toy
    before = counts(cfg)
    prompt = ids_of(3, n)
    res, gap = served(engine, ref, prompt, "a")
    assert gap < TOL and res.n_cached_tokens == 0
    (rec,), snaps, free = held(engine)
    assert rec and snaps == 0 and free == 10
    for turn in range(2):
        prompt = prompt + res.token_ids + ids_of(4 + turn, 37)
        res, gap = served(engine, ref, prompt, "a")
        assert gap < TOL and res.n_cached_tokens == len(prompt) - 37 - 1
        assert held(engine) == ([rec], 0, 10)
    assert moved(cfg, before) == {"zero": 1, "carried": 2}
    assert engine.quant_stats()["state_bytes_per_record"] \
        == 6 * (1024 + 3 * 128) * 4


def test_the_second_session_takes_the_snapshot_the_third_adopts(engine, toy):
    """A cached prefix with no snapshot at its end is prefilled again,
    counted, and leaves the snapshot; the next session adopts it — a copy
    into a record of its own — and prefills its suffix alone. Every
    session's logits are those of one that prefilled everything, and the
    first session's later turn is unchanged by its adopters."""
    cfg, _, ref = toy
    before = counts(cfg)
    shared = ids_of(5, 300)                     # two whole pages shared
    a = shared + ids_of(6, 40)
    ra, gap = served(engine, ref, a, "a")
    assert gap < TOL
    b = shared + ids_of(7, 50)
    rb, gap = served(engine, ref, b, "b")
    assert gap < TOL and rb.n_cached_tokens == 0
    assert moved(cfg, before) == {"zero": 2, "reprefill": 256, "snapshot": 1}
    assert held(engine)[1] == 1
    c = shared + ids_of(8, 60)
    rc, gap = served(engine, ref, c, "c")
    assert gap < TOL and rc.n_cached_tokens == 256
    assert moved(cfg, before) == {"zero": 2, "reprefill": 256, "snapshot": 1,
                                  "adopted": 1, "copy": 1}
    recs, snaps, free = held(engine)
    assert len(set(recs)) == 3 and snaps == 1 and free == 11 - 4
    # no shared writer: the snapshot's owner is the tree, and a, b, c each
    # go on from a record of their own
    for sid, prompt, res in (("a", a, ra), ("c", c, rc), ("b", b, rb)):
        nxt = prompt + res.token_ids + ids_of(9, 20)
        r, gap = served(engine, ref, nxt, sid)
        assert gap < TOL and r.n_cached_tokens >= len(prompt)
    # and a fourth adopts the same snapshot, untouched by all of that
    rd, gap = served(engine, ref, shared + ids_of(10, 30), "d")
    assert gap < TOL and rd.n_cached_tokens == 256
    assert held(engine)[1] == 1


def test_a_match_falls_back_to_the_deepest_snapshot(engine, toy):
    """A session that matches three cached pages where the tree holds a
    snapshot after the second starts from that one, prefills the third
    page again (counted) and leaves a snapshot at the match's end."""
    cfg, _, ref = toy
    long = ids_of(11, 420)
    for sid, cut in (("a", 420), ("b", 300)):   # b leaves one at 256
        _, gap = served(engine, ref, long[:cut] + ids_of(12, 9), sid)
        assert gap < TOL
    assert held(engine)[1] == 1
    before = counts(cfg)
    r, gap = served(engine, ref, long[:400] + ids_of(13, 30), "c")
    assert gap < TOL and r.n_cached_tokens == 256
    assert moved(cfg, before) == {"adopted": 1, "copy": 1, "reprefill": 128,
                                  "snapshot": 1}
    assert held(engine)[1] == 2
    r, gap = served(engine, ref, long[:400] + ids_of(14, 30), "d")
    assert gap < TOL and r.n_cached_tokens == 384


def test_a_prompt_that_parts_from_its_session_starts_over(engine, toy):
    """The live record is the state at the session's END: a prompt that
    leaves the session's tokens earlier cannot go on from it. The session
    is forgotten and the row starts from the cache's deepest snapshot (or
    from nothing), the rest counted as prefilled again."""
    cfg, _, ref = toy
    first = ids_of(15, 330)
    res, _ = served(engine, ref, first, "a")
    before = counts(cfg)
    turned = first[:290] + ids_of(16, 25)
    r, gap = served(engine, ref, turned, "a")
    assert gap < TOL and r.n_cached_tokens == 0
    assert moved(cfg, before) == {"zero": 1, "reprefill": 290,
                                  "snapshot": 1}
    assert held(engine)[0] != [] and len(held(engine)[0]) == 1
    # and now the cache has a snapshot at 256 that the next such turn adopts
    r, gap = served(engine, ref, first[:280] + ids_of(17, 25), "a")
    assert gap < TOL and r.n_cached_tokens == 256


def test_a_record_evicted_under_pressure_is_prefilled_again(toy):
    """Eleven usable records: twelve sessions cannot all stay. The least
    recently used goes, pages and record, and its next turn prefills its
    whole prompt again, correctly; the pool's balance holds."""
    cfg, _, ref = toy
    eng = new_engine(toy)
    before = counts(cfg)
    prompts = {f"s{i}": ids_of(20 + i, 140 + i) for i in range(12)}
    out = {}
    for sid, p in prompts.items():
        out[sid], gap = served(eng, ref, p, sid, n=3)
        assert gap < TOL
    recs, snaps, free = held(eng)
    assert len(recs) == 11 and free == 0 and snaps == 0
    assert moved(cfg, before)["evicted"] == 1
    assert "s0" not in eng.sessions._sessions
    nxt = prompts["s0"] + out["s0"].token_ids + ids_of(40, 12)
    r, gap = served(eng, ref, nxt, "s0", n=3)
    # its first page is still cached, with no snapshot at its end: all of
    # the prompt ran again (and the records it took evicted others)
    assert gap < TOL and r.n_cached_tokens == 0
    assert moved(cfg, before)["evicted"] >= 2
    assert moved(cfg, before)["reprefill"] == 128
    for sid in list(eng.sessions._sessions):
        eng.drop_session(sid)
    st = eng.sessions
    assert len(st.records._free) + st.prefix_cache.stats()[
        "cached_records"] == 11 and not st.records._refs


def test_a_batch_of_new_sessions_shares_one_prefill(engine, toy):
    """Three new sessions with two pages in common arrive in ONE call: the
    wave planner defers two of them behind the first, whose chunk forward
    leaves the snapshot where they will start — nothing is prefilled
    twice."""
    cfg, _, ref = toy
    before = counts(cfg)
    shared = ids_of(70, 290)
    prompts = [shared + ids_of(71 + i, 30 + 7 * i) for i in range(3)]
    res = engine.generate(prompts, temperature=0.0, max_new_tokens=4,
                          session_ids=["a", "b", "c"])
    for p, r in zip(prompts, res):
        ids = p + r.token_ids
        want = ref.logits(np.pad(np.asarray(ids, np.int32),
                                 (0, 640 - len(ids))),
                          np.arange(len(p) - 1, len(ids) - 1))
        assert (want.max(-1) - want[np.arange(4), r.token_ids]).max() < TOL
    assert [r.n_cached_tokens for r in res] == [0, 256, 256]
    assert moved(cfg, before) == {"zero": 1, "snapshot": 1, "adopted": 2,
                                  "copy": 2}
    assert len(set(held(engine)[0])) == 3 and held(engine)[1] == 1


def test_a_batch_of_rows_without_sessions_borrows_records(engine, toy):
    cfg, _, ref = toy
    free = held(engine)[2]
    prompts = [ids_of(50 + i, 60 + 9 * i) for i in range(3)]
    res = engine.generate(prompts, temperature=0.0, max_new_tokens=4)
    for p, r in zip(prompts, res):
        ids = p + r.token_ids
        want = ref.logits(np.pad(np.asarray(ids, np.int32),
                                 (0, 640 - len(ids))),
                          np.arange(len(p) - 1, len(ids) - 1))
        assert (want.max(-1) - want[np.arange(4), r.token_ids]).max() < TOL
    assert held(engine) == ([], 0, free)


# -- what cannot carry the records refuses by the mechanism's name ------------

REFUSALS = {
    "forward_hidden": lambda e: tr.forward_hidden(
        e.params, e.cfg, jnp.zeros((1, 4), jnp.int32),
        jnp.zeros((1, 4), jnp.int32), None, None, None),
    "host and disk KV tiers": lambda e: e.attach_tier(host_mb=8),
    "handoff": lambda e: __import__(
        "quoracle_tpu.serving.handoff", fromlist=["KVHandoff"]
    ).KVHandoff().export(e, "a", "xla:toy-nemotron"),
    "drafts": lambda e: __import__(
        "quoracle_tpu.models.speculative", fromlist=["BatchedSpeculator"]
    ).BatchedSpeculator(e, e),
    "baton drafts": lambda e: __import__(
        "quoracle_tpu.models.speculative", fromlist=["SpeculativeDecoder"]
    ).SpeculativeDecoder(e.cfg, e.params, e.cfg, e.params, e.tokenizer),
    "verify_chunk": lambda e: e.verify_chunk([[5, 6, 7]], ["v"], [1]),
}


@pytest.mark.parametrize("path", sorted(REFUSALS))
def test_a_path_that_cannot_carry_the_records_refuses(engine, path):
    with pytest.raises(ValueError) as err:
        REFUSALS[path](engine)
    assert "a pool of recurrent-state records beside the paged KV" \
        in str(err.value)
    assert "attention with no positional embedding" in str(err.value)


@pytest.mark.parametrize("kw,what", [
    ({"quantize_kv": True}, "--quantize-kv"),
    ({"quantize_weights": True}, "--quantize-weights"),
    ({"mesh": "a mesh"}, "--tp > 1"),
])
def test_an_engine_option_that_cannot_carry_it_refuses_at_start(toy, kw,
                                                                what):
    cfg, params, _ = toy
    with pytest.raises(ValueError) as err:
        GenerateEngine(cfg, f32(params), ByteTokenizer(), max_seq=512, **kw)
    assert what in str(err.value) and "records" in str(err.value)


def test_the_gather_fallback_refuses_and_leaks_nothing(engine):
    free = held(engine)[2]
    pages = engine.sessions.free_pages()
    engine._force_gather_decode = True
    with pytest.raises(RuntimeError) as err:
        engine.generate([ids_of(60, 40)], temperature=0.0, max_new_tokens=4,
                        session_ids=["g"])
    assert "gather fallback" in str(err.value)
    engine._force_gather_decode = False
    assert held(engine) == ([], 0, free)
    assert engine.sessions.free_pages() == pages
