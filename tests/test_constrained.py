"""Grammar-constrained JSON decoding (VERDICT r1 item 6): every constrained
sample must parse as a JSON object, unconstrained rows are unaffected, and
the constraint composes with sessions and the backend path."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from quoracle_tpu.models.config import get_model_config
from quoracle_tpu.models.constrained import CharDFA, JsonTokenTable, REJECT
from quoracle_tpu.models.generate import GenerateEngine
from quoracle_tpu.models.tokenizer import ByteTokenizer
from quoracle_tpu.models.transformer import init_params


# ---------------------------------------------------------------------------
# Char DFA semantics
# ---------------------------------------------------------------------------

def walk(dfa, s):
    st = dfa.start_id
    for ch in s:
        if st < 0:
            return None
        st = int(dfa.trans[st, dfa.char_index(ch)])
    return None if st < 0 else st


VALID = [
    '{"a": 1}',
    '{"action": "wait", "params": {"x": [1, 2.5e-3, true, null]}}',
    '{ }',
    '{"s": "q\\"\\\\ \\u0041"}',
    '{"a": {"b": [1, 2]}} ',
    '{"neg": -0.5, "exp": 1e10}',
    '{"two  spaces": "in  strings  are  content"}',
]
INVALID = [
    "{", '{"a" 1}', "{'a': 1}", '{"a": tru}', '{"a": 1,}',
    '{"a": "\\q"}', "hello", '{"a": 1}}', "false", "[1]", '{"a": .5}',
    # ws runs are capped at ONE char between tokens (sampling grammar:
    # unbounded ws lets a model burn its budget without emitting content)
    '{  "a": 1}', '{"a":  1}', '{"a": 1}  ', '{"a": 07}', '{"a": -012}',
]


@pytest.mark.parametrize("text", VALID)
def test_dfa_accepts_valid_objects(text):
    dfa = CharDFA()
    st = walk(dfa, text)
    assert st is not None and dfa.accept[st], text


@pytest.mark.parametrize("text", INVALID)
def test_dfa_rejects_invalid(text):
    dfa = CharDFA()
    st = walk(dfa, text)
    assert st is None or not dfa.accept[st], text


def test_depth_bound_enforced():
    dfa = CharDFA(max_depth=2)
    assert walk(dfa, '{"a": {"b": 1}}') is not None
    assert walk(dfa, '{"a": {"b": {"c": 1}}}') is None


# ---------------------------------------------------------------------------
# Token table
# ---------------------------------------------------------------------------

def test_token_table_random_walks_produce_json():
    tok = ByteTokenizer()
    tt = JsonTokenTable.for_tokenizer(tok, tok.vocab_size, tok.eos_id)
    rng = np.random.default_rng(3)
    parsed = 0
    for trial in range(20):
        st, out = tt.start_state, []
        for _ in range(300):
            allowed = np.nonzero(tt.table[st] >= 0)[0]
            assert allowed.size, "dead end"
            t = int(rng.choice(allowed))
            if t == tok.eos_id:
                break
            out.append(t)
            st = int(tt.table[st, t])
        if st >= 0 and tt.accept[st]:
            obj = json.loads(tok.decode(out))
            assert isinstance(obj, dict)
            parsed += 1
    assert parsed >= 10   # most random walks close within the cap


def test_eos_only_in_accept_states():
    tok = ByteTokenizer()
    tt = JsonTokenTable.for_tokenizer(tok, tok.vocab_size, tok.eos_id)
    assert tt.table[tt.start_state, tok.eos_id] == REJECT
    for sid in np.nonzero(tt.accept)[0]:
        assert tt.table[sid, tok.eos_id] != REJECT


# ---------------------------------------------------------------------------
# Engine integration
# ---------------------------------------------------------------------------

def make_engine():
    cfg = get_model_config("xla:tiny")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    return GenerateEngine(cfg, params, ByteTokenizer(), max_seq=256,
                          prompt_buckets=(32, 64))


def test_constrained_rows_emit_parseable_json():
    eng = make_engine()
    tok = eng.tokenizer
    prompts = [tok.encode(f"respond with json #{i}", add_bos=True)
               for i in range(3)]
    res = eng.generate(prompts, temperature=1.0, max_new_tokens=128,
                       constrain_json=[True] * 3)
    for r in res:
        if r.finish_reason == "stop":          # closed within budget
            obj = json.loads(r.text)
            assert isinstance(obj, dict)
        else:                                   # budget exhausted mid-object
            with pytest.raises(json.JSONDecodeError):
                json.loads(r.text + "#")


def test_unconstrained_rows_unaffected_in_mixed_batch():
    eng = make_engine()
    plain = make_engine()
    tok = eng.tokenizer
    prompts = [tok.encode("free text row", add_bos=True),
               tok.encode("json row", add_bos=True)]
    want = plain.generate(prompts, temperature=0.0, max_new_tokens=16)
    got = eng.generate(prompts, temperature=0.0, max_new_tokens=16,
                       constrain_json=[False, True])
    # row 0 (unconstrained) identical to a fully unconstrained engine
    assert got[0].token_ids == want[0].token_ids
    # row 1's emitted prefix must be walkable by the JSON grammar (random
    # weights may greedily emit only leading whitespace — still legal)
    dfa = CharDFA()
    st = dfa.start_id
    for ch in got[1].text:
        st = int(dfa.trans[st, dfa.char_index(ch)])
        assert st >= 0, f"illegal char {ch!r} in constrained row"


def test_constraint_composes_with_sessions():
    eng = make_engine()
    tok = eng.tokenizer
    p1 = tok.encode("round one", add_bos=True)
    r1 = eng.generate([p1], temperature=0.8, max_new_tokens=96,
                      session_ids=["a"], constrain_json=[True])[0]
    p2 = p1 + r1.token_ids + tok.encode(" refine", add_bos=False)
    r2 = eng.generate([p2], temperature=0.8, max_new_tokens=96,
                      session_ids=["a"], constrain_json=[True])[0]
    assert r2.n_cached_tokens > 0
    if r2.finish_reason == "stop":
        assert isinstance(json.loads(r2.text), dict)


def test_backend_consensus_never_parse_fails():
    """The VERDICT 'done' criterion: with masking on, consensus rounds on
    the real (random-weight) TPU backend never hit ParseFailure — every
    completed response parses."""
    from quoracle_tpu.consensus.engine import ConsensusConfig, ConsensusEngine
    from quoracle_tpu.models.runtime import TPUBackend
    backend = TPUBackend(pool=["xla:tiny", "xla:tiny-gemma"])
    eng = ConsensusEngine(backend, ConsensusConfig(
        model_pool=["xla:tiny", "xla:tiny-gemma"],
        max_refinement_rounds=0, max_tokens=96, session_key="cj-agent",
        constrained_json=True))
    msgs = {m: [{"role": "user", "content": "act"}]
            for m in ["xla:tiny", "xla:tiny-gemma"]}
    outcome = eng.decide(msgs)
    # random weights → the ACTION may be semantically invalid (unknown
    # action name), but no response may fail JSON PARSING
    for f in outcome.failures:
        assert "parse" not in f.error, f.error
    backend.close()


# ---------------------------------------------------------------------------
# Schema-aware grammar: action-enum constraint (VERDICT r2 item 7)
# ---------------------------------------------------------------------------

ENUM = ("send_message", "spawn_child", "todo", "wait")


def test_enum_dfa_accepts_only_allowed_actions():
    dfa = CharDFA(max_depth=4, action_enum=ENUM)
    ok = '{"action": "wait", "params": {"duration": 3}, "wait": true}'
    st = walk(dfa, ok)
    assert st is not None and dfa.accept[st]
    for bad in (
        '{"action": "execute_shell", "params": {}}',   # not in enum
        '{"action": "wai"}',                           # prefix only
        '{"params": {}, "action": "wait"}',            # action must be first
        '{"action": "wait", "action": "todo"}',        # duplicate key
        '{"action": "wait", "\\u0061ction": "x"}',     # escaped respelling
        '{}',                                          # action required
    ):
        st = walk(dfa, bad)
        assert st is None or not dfa.accept[st], bad


def test_enum_dfa_keeps_nested_objects_generic():
    dfa = CharDFA(max_depth=4, action_enum=ENUM)
    nested = ('{"action": "todo", "params": {"items": '
              '[{"action": "anything", "task": "x"}]}, "reasoning": "r"}')
    st = walk(dfa, nested)
    assert st is not None and dfa.accept[st]


def test_enum_token_walks_always_name_allowed_action():
    tok = ByteTokenizer()
    tt = JsonTokenTable.for_tokenizer(tok, tok.vocab_size, tok.eos_id,
                                      action_enum=ENUM)
    rng = np.random.default_rng(7)
    closed = 0
    for trial in range(20):
        st, out = tt.start_state, []
        for _ in range(400):
            allowed = np.nonzero(tt.table[st] >= 0)[0]
            assert allowed.size, "dead end"
            t = int(rng.choice(allowed))
            if t == tok.eos_id:
                break
            out.append(t)
            st = int(tt.table[st, t])
        if st >= 0 and st < len(tt.accept) and tt.accept[st]:
            obj = json.loads(tok.decode(out))
            assert obj["action"] in ENUM
            closed += 1
    assert closed >= 10


def test_engine_rows_with_enum_emit_allowed_action():
    eng = make_engine()
    tok = eng.tokenizer
    prompts = [tok.encode(f"decide #{i}", add_bos=True) for i in range(3)]
    res = eng.generate(prompts, temperature=1.0, max_new_tokens=160,
                       constrain_json=[True] * 3,
                       action_enums=[ENUM] * 3)
    for r in res:
        if r.finish_reason == "stop":
            assert json.loads(r.text)["action"] in ENUM


def test_mixed_enum_batch_stacks_grammars():
    """Rows with different enums (and a plain-JSON row) share one decode."""
    eng = make_engine()
    tok = eng.tokenizer
    prompts = [tok.encode(f"row {i}", add_bos=True) for i in range(3)]
    res = eng.generate(prompts, temperature=1.0, max_new_tokens=160,
                       constrain_json=[True, True, True],
                       action_enums=[("wait",), ("todo", "orient"), None])
    for r, allowed in zip(res, [("wait",), ("todo", "orient"), None]):
        if r.finish_reason == "stop":
            obj = json.loads(r.text)
            if allowed is not None:
                assert obj["action"] in allowed


def test_consensus_engine_threads_action_enum_to_backend():
    from quoracle_tpu.consensus.engine import ConsensusConfig, ConsensusEngine
    from quoracle_tpu.models.runtime import MockBackend
    backend = MockBackend()
    eng = ConsensusEngine(backend, ConsensusConfig(
        model_pool=list(MockBackend.DEFAULT_POOL),
        allowed_actions={"wait", "todo"}, constrained_json=True))
    eng.decide({m: [{"role": "user", "content": "x"}]
                for m in MockBackend.DEFAULT_POOL})
    assert all(c.action_enum == ("todo", "wait") for c in backend.calls)
