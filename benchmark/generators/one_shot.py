"""Closed-loop one-shot requests: every request is a new session with a
prompt of its own, nothing shared, a few tokens asked.

Parameters (the traffic file): `clients`, `prompt_tokens` [lo, hi]
(log-uniform, the whole prompt as the model sees it), `max_tokens`,
`think_ms_mean` (0 = none), `greedy_one_in`, `temperature`, `block`.
"""

from __future__ import annotations

from benchmark import draws
from benchmark.traffic import Turn


class Client:
    def __init__(self, name, turns):
        self.name = name
        self.turns = turns            # (prompt_text, max_tokens, think_s, temp)
        self.n_turn = 0
        self.sid = None

    def next(self, prev) -> Turn:
        prompt, max_tokens, think_s, temp = self.turns[self.n_turn]
        drop = (self.sid,) if self.sid else ()
        self.sid = f"{self.name}-r{self.n_turn}"
        self.n_turn += 1
        return Turn([{"role": "user", "content": prompt}], self.sid,
                    max_tokens, temp, think_s, drop, new_session=True)


def build(params: dict, seed: int, n_turns: int, text) -> list:
    block = int(params.get("block", 20))
    lo, hi = params["prompt_tokens"]
    # the chat template wraps the text in a few tokens of its own
    glue = text.count_chat_glue()
    clients = []
    n_clients = int(params["clients"])
    for c in range(n_clients):
        name = f"client{c}"
        phase = (c + 0.5) / n_clients
        u_len = draws.stratified(seed, f"{name}:len", n_turns, block, phase)
        u_think = draws.stratified(seed, f"{name}:think", n_turns, block,
                                   phase)
        greedy = draws.stratified(seed, f"{name}:greedy", n_turns,
                                  int(params["greedy_one_in"]))
        turns = []
        for i in range(n_turns):
            n = int(round(draws.log_uniform(u_len[i], lo, hi))) - glue
            think = (draws.exponential(u_think[i],
                                       params["think_ms_mean"] / 1000.0)
                     if params.get("think_ms_mean") else 0.0)
            temp = (0.0 if greedy[i] < 1.0 / params["greedy_one_in"]
                    else float(params["temperature"]))
            turns.append((text.make(f"{name}:prompt:{i}", n),
                          int(params["max_tokens"]), think, temp))
        clients.append(Client(name, turns))
    return clients
