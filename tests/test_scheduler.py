"""Decode-level continuous batching (models/scheduler.py; VERDICT r4
item 4): rows join/leave a shared chunked decode loop, with KV sessions +
resumable grammar state as the cross-chunk row state. Temperature-0 rows
must be BIT-IDENTICAL to a one-shot generate."""

import json
import time

import jax
import jax.numpy as jnp

from quoracle_tpu.models.config import get_model_config
from quoracle_tpu.models.generate import GenerateEngine
from quoracle_tpu.models.scheduler import ContinuousBatcher
from quoracle_tpu.models.tokenizer import ByteTokenizer
from quoracle_tpu.models.transformer import init_params


def make_engine(**kw):
    cfg = get_model_config("xla:tiny")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    return GenerateEngine(cfg, params, ByteTokenizer(),
                          max_seq=kw.pop("max_seq", 256),
                          prompt_buckets=kw.pop("prompt_buckets",
                                                (32, 64, 128)), **kw)


def enc(text):
    return ByteTokenizer().encode(text, add_bos=True)


def test_chunked_continuation_matches_one_shot_greedy():
    """One row, chunk=4: the chunked stream (session resume + 1-token
    re-prefill per chunk) must reproduce the one-shot greedy tokens."""
    eng = make_engine()
    p = enc("user: tell me a long story now")
    want = eng.generate([p], temperature=0.0, max_new_tokens=24)[0]
    cb = ContinuousBatcher(eng, chunk=4)
    try:
        got = cb.submit(p, temperature=0.0, max_new_tokens=24).result(120)
    finally:
        cb.close()
    assert got.token_ids == want.token_ids
    assert got.finish_reason == want.finish_reason
    assert len(eng.sessions) == 0          # owned session dropped


def test_constrained_rows_resume_grammar_across_chunks():
    """A grammar-constrained row split over chunks must still emit one
    valid JSON object — the relative json_state handoff."""
    eng = make_engine()
    p = enc("user: respond with json")
    want = eng.generate([p], temperature=0.0, max_new_tokens=48,
                        constrain_json=[True])[0]
    cb = ContinuousBatcher(eng, chunk=5)
    try:
        got = cb.submit(p, temperature=0.0, max_new_tokens=48,
                        constrain_json=True).result(180)
    finally:
        cb.close()
    assert got.token_ids == want.token_ids
    # the emitted prefix parses as (or extends to) valid JSON exactly as
    # the one-shot output does
    assert got.text == want.text


def test_row_admitted_mid_stream():
    """Row B submitted while row A decodes must join A's loop (not wait
    for A's full round) and still produce B's solo greedy tokens."""
    eng = make_engine()
    pa = enc("user: the first agent's question is long and involved")
    pb = enc("user: second agent arrives later")
    want_a = eng.generate([pa], temperature=0.0, max_new_tokens=32)[0]
    want_b = eng.generate([pb], temperature=0.0, max_new_tokens=8)[0]

    cb = ContinuousBatcher(eng, chunk=4)
    try:
        fa = cb.submit(pa, temperature=0.0, max_new_tokens=32)
        # let A's first chunks start, then admit B mid-stream
        time.sleep(0.3)
        fb = cb.submit(pb, temperature=0.0, max_new_tokens=8)
        got_a, got_b = fa.result(180), fb.result(180)
    finally:
        cb.close()
    assert got_a.token_ids == want_a.token_ids
    assert got_b.token_ids == want_b.token_ids
    assert len(eng.sessions) == 0


def test_mixed_action_enums_across_chunks():
    """Rows with DIFFERENT action enums share chunk calls (stacked
    grammar tables); relative states must survive restacking as rows
    join/leave."""
    eng = make_engine()
    p1 = enc("user: act one")
    p2 = enc("user: act two")
    e1, e2 = ("alpha", "beta"), ("gamma",)
    want1 = eng.generate([p1], temperature=0.0, max_new_tokens=40,
                         constrain_json=[True], action_enums=[e1])[0]
    want2 = eng.generate([p2], temperature=0.0, max_new_tokens=40,
                         constrain_json=[True], action_enums=[e2])[0]
    cb = ContinuousBatcher(eng, chunk=6)
    try:
        f1 = cb.submit(p1, temperature=0.0, max_new_tokens=40,
                       constrain_json=True, action_enum=e1)
        f2 = cb.submit(p2, temperature=0.0, max_new_tokens=40,
                       constrain_json=True, action_enum=e2)
        got1, got2 = f1.result(240), f2.result(240)
    finally:
        cb.close()
    assert got1.token_ids == want1.token_ids
    assert got2.token_ids == want2.token_ids


def test_backend_continuous_mode_end_to_end():
    """TPUBackend: consensus-shaped sessioned requests
    flow through the shared decode loop; refinement rounds keep their
    session residency."""
    from quoracle_tpu.models.runtime import QueryRequest, TPUBackend
    backend = TPUBackend(pool=["xla:tiny"], continuous_chunk=4)
    msgs = [{"role": "user", "content": "hello continuous world"}]
    r1 = backend.query([
        QueryRequest("xla:tiny", msgs, temperature=0.0, max_tokens=12,
                     session_id="agent-1"),
        QueryRequest("xla:tiny", msgs, temperature=0.0, max_tokens=12,
                     session_id="agent-2"),
    ])
    assert all(r.ok for r in r1), [r.error for r in r1]
    assert r1[0].text == r1[1].text          # same prompt, greedy
    msgs2 = msgs + [{"role": "assistant", "content": r1[0].text},
                    {"role": "user", "content": "refine."}]
    r2 = backend.query([QueryRequest("xla:tiny", msgs2, temperature=0.0,
                                     max_tokens=12, session_id="agent-1")])
    assert r2[0].ok, r2[0].error
    eng = backend.engines["xla:tiny"]
    assert eng.sessions.get("agent-1") is not None   # session retained
    backend.close()


def test_row_at_context_edge_retires_without_poisoning_batch():
    """A row whose remaining window is an exact chunk multiple must retire
    at the window edge instead of submitting a max_seq-length continuation
    that would ContextOverflow the whole shared batch."""
    eng = make_engine(max_seq=128, prompt_buckets=(32, 64, 128))
    tok = ByteTokenizer()
    edge = tok.encode("x" * 90, add_bos=True)   # window remainder ≈ chunks
    other = enc("user: a small neighbor")
    cb = ContinuousBatcher(eng, chunk=8)
    try:
        fe = cb.submit(edge, temperature=0.0, max_new_tokens=200)
        fo = cb.submit(other, temperature=0.0, max_new_tokens=8)
        ge, go = fe.result(240), fo.result(240)
    finally:
        cb.close()
    # edge row stopped at the window, neighbor unharmed
    assert len(edge) + len(ge.token_ids) <= 128
    assert go.n_gen_tokens >= 1


def test_over_window_submit_fails_only_its_future():
    """ADVICE r4 #1 regression: a directly-submitted prompt >= max_seq must
    fail ITS OWN future at admission (ContextOverflowError) while a
    concurrent normal row completes — one bad agent must never poison the
    other agents' in-flight rows in a shared chunk."""
    from quoracle_tpu.models.generate import ContextOverflowError
    eng = make_engine(max_seq=128, prompt_buckets=(32, 64, 128))
    tok = ByteTokenizer()
    cb = ContinuousBatcher(eng, chunk=8)
    try:
        ok_row = cb.submit(enc("user: hello"), temperature=0.0,
                           max_new_tokens=8)
        bad = cb.submit(tok.encode("y" * 400, add_bos=True),
                        temperature=0.0, max_new_tokens=8)
        try:
            bad.result(10)
            raise AssertionError("over-window submit must fail")
        except ContextOverflowError:
            pass
        good = ok_row.result(240)
    finally:
        cb.close()
    assert good.n_gen_tokens >= 1


def test_close_mid_chunk_leaves_no_stranded_future():
    """ADVICE r4 #2 regression: close() while the worker is mid-chunk must
    not race the worker's set_result (InvalidStateError) — every submitted
    future ends DONE (result or clean failure), never stranded, and a
    post-close submit fails loudly."""
    eng = make_engine(max_seq=256, prompt_buckets=(32, 64, 128))
    cb = ContinuousBatcher(eng, chunk=4)
    futs = [cb.submit(enc(f"user: task {i}"), temperature=0.0,
                      max_new_tokens=64) for i in range(3)]
    # let the worker pick the rows up and enter a device chunk
    time.sleep(0.3)
    cb.close()
    for f in futs:
        try:
            r = f.result(120)          # done: finished result...
            assert r.n_gen_tokens >= 0
        except RuntimeError as e:      # ...or the documented close failure
            assert "closed" in str(e).lower()
    try:
        cb.submit(enc("user: late"), temperature=0.0, max_new_tokens=4)
        raise AssertionError("submit after close must fail")
    except RuntimeError:
        pass


def test_submit_racing_close_never_strands_futures():
    """ISSUE 3 satellite: submits racing close() must never strand a
    future. The reject-after-closed check runs UNDER the batcher lock —
    close() flips _stop under the same lock, so every row that made it
    into the queue is covered by close()'s drain and every later submit
    raises. Each accepted future must end DONE (result or the documented
    close failure); none may hang."""
    import threading
    from concurrent.futures import wait

    eng = make_engine()
    cb = ContinuousBatcher(eng, chunk=4)
    accepted: list = []
    acc_lock = threading.Lock()
    closed = threading.Event()

    def spam(k):
        i = 0
        while not closed.is_set() and i < 200:
            try:
                f = cb.submit(enc(f"user: race {k}-{i}"), temperature=0.0,
                              max_new_tokens=2)
            except RuntimeError:
                return                    # closed: the documented rejection
            with acc_lock:
                accepted.append(f)
            i += 1

    threads = [threading.Thread(target=spam, args=(k,)) for k in range(4)]
    for t in threads:
        t.start()
    time.sleep(0.25)                      # let submits + chunks interleave
    cb.close()
    closed.set()
    for t in threads:
        t.join(30)
        assert not t.is_alive()
    assert accepted, "race produced no submissions"
    done, not_done = wait(accepted, timeout=120)
    assert not not_done, f"{len(not_done)} futures stranded"
    for f in accepted:
        exc = f.exception()
        if exc is not None:               # queued at close: fails loudly
            assert "closed" in str(exc).lower()
    assert len(eng.sessions) == 0         # every owned session dropped


def test_credential_duplicate_model_spec_is_deterministic(caplog):
    """ADVICE r4 #4 regression: two credentials for one model_spec resolve
    to the lowest id (stable across engines/plans) and WARN about the
    duplicate instead of silently picking an arbitrary row."""
    import logging

    from quoracle_tpu.persistence.db import Database
    from quoracle_tpu.persistence.store import CredentialStore
    db = Database(":memory:", encryption_key="unit-test-key")
    store = CredentialStore(db)
    store.put("b-second", {"type": "bearer", "token": "tok-b"},
              model_spec="api:svc")
    store.put("a-first", {"type": "bearer", "token": "tok-a"},
              model_spec="api:svc")
    with caplog.at_level(logging.WARNING):
        data = store.for_model("api:svc")
    assert data["token"] == "tok-a"            # lowest id wins, always
    assert any("credentials" in r.message and "api:svc" in r.message
               for r in caplog.records)


def test_sessionless_generate_runs_without_paged_lock():
    """ADVICE r4 #3 regression: image rows in continuous mode call the
    engine directly and SESSIONLESS — that call must not need
    engine._paged_lock (the grammar cache has its own lock), or a long
    VLM round would stall every concurrent text agent's sessioned chunks
    for its whole duration. Holding the lock here and completing anyway
    proves the sessionless path never touches it."""
    import threading

    eng = make_engine(max_seq=128, prompt_buckets=(32, 64, 128))
    done = threading.Event()
    out = {}

    def run():
        out["r"] = eng.generate([enc("user: describe")], temperature=0.0,
                                max_new_tokens=8, constrain_json=[True])[0]
        done.set()

    with eng._paged_lock:                   # a text agent mid-chunk
        t = threading.Thread(target=run, daemon=True)
        t.start()
        assert done.wait(120), \
            "sessionless generate blocked on engine._paged_lock"
    assert out["r"].n_gen_tokens >= 1


def test_default_backend_serves_a_text_row_through_the_batcher():
    """One batcher (ISSUE 46): a TPUBackend built with no argument but its
    pool serves a text row through the member's ContinuousBatcher — the
    batcher retires it and the row ring holds its record."""
    from quoracle_tpu.infra import introspect
    from quoracle_tpu.models.runtime import QueryRequest, TPUBackend
    from quoracle_tpu.models.scheduler import ContinuousBatcher
    backend = TPUBackend(pool=["xla:tiny"])
    try:
        assert isinstance(backend._cbatchers["xla:tiny"], ContinuousBatcher)
        before = backend.scheduler_stats()["xla:tiny"]
        assert before["retired"] == 0 and before["steps"] == 0
        introspect.reset()
        introspect.enable()
        r = backend.query([QueryRequest(
            "xla:tiny", [{"role": "user", "content": "one batcher"}],
            temperature=0.0, max_tokens=6)])[0]
        assert r.ok, r.error
        after = backend.scheduler_stats()["xla:tiny"]
        assert after["retired"] == 1 and after["steps"] >= 1
        rows = introspect.row_ring()
        assert len(rows) == 1 and rows[0]["model"] == "tiny"
        assert rows[0]["emitted_tokens"] == r.usage.completion_tokens
        assert rows[0]["prompt_tokens"] == r.usage.prompt_tokens
    finally:
        backend.close()
        introspect.reset()
