"""The generators: the same seed gives the same requests, another seed
gives the same lengths in another order, and the lengths realise the
distributions the traffic files state."""

import statistics

from benchmark import draws, traffic
from quoracle_tpu.models.tokenizer import ByteTokenizer


class Result:
    """What a client reads of a finished turn."""
    ok = True

    def __init__(self, prompt, completion, text="ok"):
        self.usage = type("U", (), {"prompt_tokens": prompt,
                                    "completion_tokens": completion})()
        self.text = text


def build(mix_name, seed, n_turns=40):
    mix = traffic.load_traffic(mix_name)
    gen = traffic.load_generator(mix["kind"])
    text = traffic.SeededText(ByteTokenizer(), seed)
    return mix, text, gen.build(mix["params"], seed, n_turns, text)


def walk(clients, text, n):
    """Drive every client n turns with a stand-in for the model."""
    out = []
    for c in clients:
        prev = None
        for _ in range(n):
            t = c.next(prev)
            n_prompt = sum(text.count(m["content"]) for m in t.messages)
            out.append((c.name, t.session_id, t.max_tokens, t.temperature,
                        round(t.think_s, 9), n_prompt, t.new_session,
                        t.messages[-1]["content"]))
            prev = Result(n_prompt, t.max_tokens)
    return out


def test_same_seed_same_requests_other_seed_other_order():
    def requests(seed):
        _, text, clients = build("tiny-turns", seed)
        return walk(clients, text, 12)
    assert requests(7) == requests(7)
    assert requests(7) != requests(8)
    # the seed reorders the work, it does not change it: every client's
    # first block holds the same max_tokens, whatever the seed
    blk = traffic.load_traffic("tiny-turns")["params"]["block"]
    want = sorted(t[1] for t in build("tiny-turns", 7, blk)[2][0].turns)
    for seed in (8, 2 ** 31 + 5):
        for c in build("tiny-turns", seed, blk)[2]:
            assert sorted(t[1] for t in c.turns) == want


def test_agent_sessions_realise_the_stated_lengths():
    mix, text, clients = build("agent-turns", 11, n_turns=40)
    p = mix["params"]
    tools = [text.count(t[0]) for c in clients for t in c.turns]
    lo, hi = p["tool_tokens"]
    # byte tokenizer: the cut is exact
    assert lo - 1 <= min(tools) and max(tools) <= hi
    # log-uniform on [16, 512]: median sqrt(16 * 512) = 90.5
    assert 80 <= statistics.median(tools) <= 100
    mt = [t[1] for c in clients for t in c.turns]
    assert set(mt) == set(p["max_tokens"]["values"])
    # weights 3:4:2:1 over 32, 64, 96, 128: mean 67.2
    assert abs(statistics.mean(mt) - 67.2) < 1
    think = [t[2] for c in clients for t in c.turns[1:]]
    assert abs(statistics.mean(think) - p["think_ms_mean"] / 1000) < 0.03
    greedy = [t[3] == 0.0 for c in clients for t in c.turns]
    assert abs(sum(greedy) / len(greedy) - 1 / p["greedy_one_in"]) < 0.01
    assert len(clients) == p["agents"]


def test_agent_session_grows_then_ends_at_its_cap():
    mix, text, clients = build("tiny-turns", 3, n_turns=60)
    c = clients[0]
    prev, sids, sizes = None, [], []
    for _ in range(40):
        t = c.next(prev)
        n_prompt = sum(text.count(m["content"]) for m in t.messages) + 20
        sids.append(t.session_id)
        sizes.append(n_prompt)
        if t.new_session and len(sids) > 1:
            assert t.drop == (sids[-2],)
            assert len(t.messages) == 2
        prev = Result(n_prompt, t.max_tokens, text="x" * t.max_tokens)
    assert len(set(sids)) > 1                      # sessions do end
    cap = mix["params"]["session_cap_tokens"]
    assert max(sizes) < cap + 200                  # and soon after the cap
    # within a session every turn appends the answer and a tool result
    first = sids[0]
    grow = [s for s, sid in zip(sizes, sids) if sid == first]
    assert grow == sorted(grow)


def test_one_shot_prompts_are_new_unshared_and_log_uniform():
    mix, text, clients = build("cold-prompts", 5, n_turns=40)
    p = mix["params"]
    assert len(clients) == p["clients"]
    glue = text.count_chat_glue()
    lens = [text.count(t[0]) + glue for c in clients for t in c.turns]
    lo, hi = p["prompt_tokens"]
    assert lo - 1 <= min(lens) and max(lens) <= hi
    assert 1900 <= statistics.median(lens) <= 2200     # sqrt(1024 * 4096)
    firsts = {t[0][:40] for c in clients for t in c.turns}
    assert len(firsts) == len(lens)                    # no shared prefix
    c = clients[0]
    a, b = c.next(None), c.next(Result(10, 16))
    assert a.session_id != b.session_id and b.drop == (a.session_id,)
    assert a.new_session and b.new_session and a.think_s == 0.0


def test_draws_are_the_copied_functions_and_stratify():
    from quoracle_tpu.sim import workload
    for n in range(5):
        assert draws.draw(9, "s", n) == workload.draw(9, "s", n)
        assert draws.draw_exp(9, "s", n, 2.0) == workload.draw_exp(
            9, "s", n, 2.0)
        assert draws.draw_int(9, "s", n, 3, 11) == workload.draw_int(
            9, "s", n, 3, 11)
    u = draws.stratified(2 ** 31 + 9, "x", 40, 20)
    assert sorted(u[:20]) == sorted(u[20:]) == [(i + .5) / 20
                                                for i in range(20)]
    assert u[:20] != u[20:]
    assert sorted(draws.permutation(5, "p", 9)) == list(range(9))
