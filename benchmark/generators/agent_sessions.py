"""Closed-loop agent sessions: a shared system prompt, a task, then short
tool turns until the session passes its cap and the agent opens a new one.

Parameters (the traffic file): `agents`, `task_tokens` [lo, hi],
`tool_tokens` [lo, hi] (log-uniform), `max_tokens` {values, weights},
`think_ms_mean` (exponential), `session_cap_tokens`, `greedy_one_in` (one
turn in so many is sent at temperature 0, for the output check),
`temperature`, `start_stagger_s`, `block` (stratification block).
"""

from __future__ import annotations

import os

from benchmark import draws
from benchmark.traffic import HERE, Turn


class Client:
    def __init__(self, name, system, tasks, turns, cap, first_cap):
        self.name = name
        self.system = system
        self.tasks = tasks            # task texts, one per session
        self.turns = turns            # (tool_text, max_tokens, think_s, temp)
        self.cap = cap
        self.first_cap = first_cap    # the first session ends early, so that
        self.n_session = 0            # agents do not end sessions in step
        self.n_turn = 0
        self.sid = None
        self.messages = []

    def next(self, prev) -> Turn:
        tool, max_tokens, think_s, temp = self.turns[self.n_turn]
        self.n_turn += 1
        cap = self.first_cap if self.n_session == 1 else self.cap
        used = 0 if prev is None else (prev.usage.prompt_tokens
                                       + prev.usage.completion_tokens)
        if self.sid is None or prev is None or not prev.ok or used >= cap:
            drop = (self.sid,) if self.sid else ()
            self.sid = f"{self.name}-s{self.n_session}"
            self.messages = [
                {"role": "system", "content": self.system},
                {"role": "user", "content": self.tasks[self.n_session]}]
            self.n_session += 1
            return Turn(list(self.messages), self.sid, max_tokens, temp,
                        think_s, drop, new_session=True)
        self.messages += [{"role": "assistant", "content": prev.text},
                          {"role": "user", "content": tool}]
        return Turn(list(self.messages), self.sid, max_tokens, temp, think_s)


def build(params: dict, seed: int, n_turns: int, text) -> list:
    with open(os.path.join(HERE, params["system_prompt"])) as f:
        system = f.read()
    block = int(params.get("block", 20))
    lo_t, hi_t = params["tool_tokens"]
    lo_k, hi_k = params["task_tokens"]
    mt = params["max_tokens"]
    n_agents = int(params["agents"])
    cap = int(params["session_cap_tokens"])
    clients = []
    first_caps = draws.stratified(seed, "first-cap", n_agents, n_agents)
    for a in range(n_agents):
        name = f"agent{a}"
        phase = (a + 0.5) / n_agents
        u_tool = draws.stratified(seed, f"{name}:tool", n_turns, block,
                                  phase)
        u_max = draws.stratified(seed, f"{name}:max", n_turns, block)
        u_think = draws.stratified(seed, f"{name}:think", n_turns, block,
                                   phase)
        u_task = draws.stratified(seed, f"{name}:task", n_turns, block,
                                  phase)
        greedy = draws.stratified(seed, f"{name}:greedy", n_turns,
                                  int(params["greedy_one_in"]))
        turns = []
        for i in range(n_turns):
            n_tool = int(round(draws.log_uniform(u_tool[i], lo_t, hi_t)))
            think = draws.exponential(u_think[i],
                                      params["think_ms_mean"] / 1000.0)
            if i == 0:
                think += a * float(params.get("start_stagger_s", 0.0))
            temp = (0.0 if greedy[i] < 1.0 / params["greedy_one_in"]
                    else float(params["temperature"]))
            turns.append((text.make(f"{name}:tool:{i}", n_tool),
                          int(draws.weighted(u_max[i], mt["values"],
                                             mt["weights"])),
                          think, temp))
        # a session lasts a few turns at least, so n_turns tasks are enough
        tasks = [text.make(f"{name}:task:{i}",
                           int(round(lo_k + u_task[i] * (hi_k - lo_k))))
                 for i in range(n_turns)]
        # the system prompt and a task are about 1.9k tokens: first caps
        # spread from there to the cap
        lo_cap = text.count(system) + hi_k + 150
        clients.append(Client(name, system, tasks, turns, cap,
                              int(lo_cap + first_caps[a] * (cap - lo_cap))))
    return clients
