"""The generator: lengths are a fixed multiset, the seed decides only token
ids and who starts where (rule 3), and a session carries what came back."""

import os

import numpy as np
import pytest

from benchmark.traffic import ClientSession, ClosedLoopPlan, lm_batches

from bench_helpers import ROOT, load

TRAFFIC = os.path.join(ROOT, "benchmark", "traffic")
VOCAB = 32768


def plan(name, seed):
    return ClosedLoopPlan(load(os.path.join(TRAFFIC, name + ".json")), seed, VOCAB)


@pytest.mark.parametrize("name", ["chat-sessions", "docs-batch"])
def test_two_seeds_same_lengths_other_tokens(name):
    a, b = plan(name, 1), plan(name, 2**31 + 9)
    assert a.lengths(12) == b.lengths(12)
    assert a.start != b.start or a.clients == 1
    assert sorted(a.start) == sorted(b.start) == list(range(a.clients))
    assert a.user_message(0, 0) != b.user_message(0, 0)
    assert a.user_message(0, 0) == plan(name, 1).user_message(0, 0)
    assert all(0 < t < VOCAB for t in a.user_message(0, 0))


def test_chat_turns_are_in_equal_share_and_the_means_are_the_stated_ones():
    p = plan("chat-sessions", 5)
    span = p.turns                             # one whole cycle of the plan
    turns = [p.turn(c, n) for c in range(p.clients) for n in range(span)]
    for index in range(p.turns):
        assert sum(1 for t in turns if t.turn == index) == len(turns) // p.turns
    assert {t.prompt_tokens for t in turns if t.turn == 0} == {384}
    assert sum(t.prompt_tokens for t in turns) / len(turns) == 1024
    assert sum(t.max_tokens for t in turns) / len(turns) == 128
    assert p.longest_context() == 1792
    assert sorted({t.prompt_tokens for t in turns}) == [384, 640, 896, 1152, 1408, 1664]
    assert p.system_prompt == plan("chat-sessions", 5).system_prompt
    assert len(p.system_prompt) == 256


def test_docs_lengths_cycle_per_client():
    p = plan("docs-batch", 5)
    cycle = p.user_cycle
    for client in range(p.clients):
        got = [p.turn(client, n).prompt_tokens for n in range(len(cycle))]
        assert sorted(got) == sorted(cycle)
        assert p.turn(client, 0).prompt_tokens == cycle[p.start[client] % len(cycle)]


def test_session_prompt_carries_the_answers_returned():
    p = plan("chat-sessions", 3)
    client = p.start.index(0)              # the client that starts a fresh session
    session = ClientSession(p, client)
    turn, prompt = session.next_prompt()
    assert turn.turn == 0 and len(prompt) == 384
    assert prompt[:256] == p.system_prompt
    answer = list(range(1000, 1000 + turn.max_tokens))
    session.answered(answer)
    turn2, prompt2 = session.next_prompt()
    assert turn2.turn == 1 and len(prompt2) == 384 + turn.max_tokens + 128
    assert prompt2[:384] == prompt and prompt2[384:384 + turn.max_tokens] == answer


def test_mid_session_start_has_a_seeded_history():
    p = plan("chat-sessions", 3)
    client = p.start.index(4)
    turn, prompt = ClientSession(p, client).next_prompt()
    assert turn.turn == 4 and len(prompt) == turn.prompt_tokens
    history = turn.prompt_tokens - 256 - 128       # four user messages and four answers
    assert history == 4 * 128 + 4 * p.max_tokens
    assert prompt[:256] == p.system_prompt


def test_a_finished_session_is_replaced_by_a_new_one():
    p = plan("chat-sessions", 3)
    client = p.start.index(5)
    session = ClientSession(p, client)
    turn, _ = session.next_prompt()
    session.answered([7] * turn.max_tokens)
    turn, prompt = session.next_prompt()
    assert turn.turn == 0 and len(prompt) == 384


def test_lm_batches_follow_the_seed():
    spec = {"batch": 4, "seq": 16, "zipf_a": 1.1}
    a = next(lm_batches(spec, 1, 1000))["tokens"]
    again = next(lm_batches(spec, 1, 1000))["tokens"]
    other = next(lm_batches(spec, 2**31 + 3, 1000))["tokens"]
    assert a.shape == (4, 17) and a.dtype == np.int32
    assert np.array_equal(a, again) and not np.array_equal(a, other)
    assert a.min() >= 0 and a.max() < 1000
    stream = lm_batches(spec, 1, 1000)
    assert not np.array_equal(next(stream)["tokens"], next(stream)["tokens"])


def test_a_kind_is_its_module_and_an_unknown_kind_is_refused():
    from benchmark import run
    from benchmark.kinds import closed_loop, lm_steps

    assert run.kind_module({"kind": "closed_loop"}) is closed_loop
    assert run.kind_module({"kind": "lm_steps"}) is lm_steps
    for spec in ({"kind": "open_loop"}, {}):
        with pytest.raises(ValueError, match="unknown traffic kind"):
            run.kind_module(spec)


def test_more_clients_than_lanes_is_a_plan_with_a_queue():
    """The generator does not refuse a queue: the engine's admission is
    part of what a cell may measure (queue_wait_p90_ms)."""
    from benchmark.kinds.closed_loop import plan_for

    conf = {"vocab_size": 100, "engine": {"max_slots": 2, "max_pages_per_slot": 4, "page_size": 8}}
    spec = {"kind": "closed_loop", "clients": 5, "turns": 2, "system_prompt_tokens": 4,
            "user_tokens": [8], "max_tokens": 2}
    assert plan_for(spec, conf, seed=1).clients == 5
    assert plan_for(spec, conf, seed=1, clients=7).clients == 7
    with pytest.raises(ValueError, match="per-slot capacity"):
        plan_for(dict(spec, max_tokens=12), conf, seed=1)     # 4 + 8 + 12 + 8 + 12 > 32


def test_the_window_opens_only_when_every_client_has_an_answer():
    """Rule 1, on a stand-in server whose clients are served at different
    speeds: `wait_steady` returns after the slowest client's first answer,
    and what was answered before is lead-in, outside any window."""
    import time
    import types

    from benchmark import counting
    from benchmark.kinds.closed_loop import ClosedLoop, plan_for

    conf = {"vocab_size": 100, "engine": {"max_slots": 4, "max_pages_per_slot": 8, "page_size": 8}}
    spec = {"kind": "closed_loop", "clients": 3, "turns": 1, "system_prompt_tokens": 0,
            "user_tokens": [8, 12, 16], "max_tokens": 3}

    def stream_generate(payload):
        time.sleep(0.01 * len(payload["prompt_tokens"]))       # longer prompts answer later
        return [{"token": 7}] * payload["max_tokens"] + [{"done": True}]

    system = types.SimpleNamespace(
        _ray=types.SimpleNamespace(get=lambda ref, timeout: ref),
        stream=types.SimpleNamespace(
            stream_generate=types.SimpleNamespace(remote=stream_generate)))
    loop = ClosedLoop(system, plan_for(spec, conf, seed=4))
    loop.start()
    loop.wait_steady(timeout=30)
    t0 = time.perf_counter()
    time.sleep(0.5)
    loop.stop(timeout=30)
    first_answers = [min(r.t_done for r in loop.records if r.client == c and r.t_done)
                     for c in range(3)]
    assert max(first_answers) <= t0                     # every client was answered before t0
    lead_in = [r for r in loop.records if r.t_done is not None and r.t_done < t0]
    assert len(lead_in) >= 3
    counted = counting.finished_in_window(loop.records, t0, t0 + 0.5)
    assert counted and not {id(r) for r in counted} & {id(r) for r in lead_in}
    assert all(r.error is None and r.n_out == 3 for r in loop.records if r.t_done)
