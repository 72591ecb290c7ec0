"""The one traffic generator: a data file of parameters + a seed -> the work.

Three rules keep two runs comparable (PERF.md, "the six rules"):
lengths are a fixed multiset written in the traffic file, `--seed` decides
only token ids and which client starts where, and every client walks the
same cyclic plan, so two seeds offer the same work in another order.

A traffic file names its `kind`; benchmark/kinds/<kind>.py drives the
system with what this module draws (`ClosedLoopPlan` and `ClientSession`
for `closed_loop`, `lm_batches` for `lm_steps`).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Iterator, List, Sequence

import numpy as np

SEED_MASK = (1 << 63) - 1


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """Independent generator for (seed, stream...): any whole seed, large
    ones included, gives a valid SeedSequence."""
    return np.random.default_rng([int(seed) & SEED_MASK, *[int(s) for s in stream]])


def tokens(rng: np.random.Generator, n: int, vocab: int) -> List[int]:
    # id 0 is left out: engines tend to treat it as padding
    return [int(t) for t in rng.integers(1, vocab, size=n)]


# ------------------------------------------------------------- closed loop


@dataclasses.dataclass
class Turn:
    """One request of a client's plan. `history_turns` earlier turns of the
    same session precede it in the prompt (their user messages and the
    answers the server really returned)."""

    client: int
    ordinal: int          # n-th request this client sends
    turn: int             # 0-based turn inside its session
    user_tokens: int
    prompt_tokens: int    # system + history + this user message
    max_tokens: int


class ClosedLoopPlan:
    """Client i's n-th request is step i + n of one cyclic plan: turn
    (i + n) % turns of a session, with the user message length
    user_tokens[(i + n) % len(user_tokens)]. So every turn length is
    always present in equal share, and a client that starts mid-session
    gets a seeded history of the right length. Every answer is exactly
    `max_tokens` long and a client sends its next request at once."""

    def __init__(self, spec: dict, seed: int, vocab: int):
        self.clients = int(spec["clients"])
        self.turns = int(spec["turns"])
        self.system_tokens = int(spec["system_prompt_tokens"])
        self.user_cycle = [int(n) for n in spec["user_tokens"]]
        self.max_tokens = int(spec["max_tokens"])
        self.vocab = vocab
        self.seed = seed
        self.system_prompt = tokens(rng_for(seed, 1), self.system_tokens, vocab)
        # which plan position each client starts at: a seeded permutation,
        # so the multiset of starting points is the same for every seed
        self.start = [int(s) for s in rng_for(seed, 2).permutation(self.clients)]

    def step(self, client: int, ordinal: int) -> int:
        return self.start[client] + ordinal

    def turn(self, client: int, ordinal: int) -> Turn:
        step = self.step(client, ordinal)
        turn = step % self.turns
        user = self.user_cycle[step % len(self.user_cycle)]
        history = sum(
            self.user_cycle[(step - turn + t) % len(self.user_cycle)] + self.max_tokens
            for t in range(turn)
        )
        return Turn(client, ordinal, turn, user,
                    self.system_tokens + history + user, self.max_tokens)

    def user_message(self, client: int, ordinal: int) -> List[int]:
        t = self.turn(client, ordinal)
        return tokens(rng_for(self.seed, 3, client, ordinal), t.user_tokens, self.vocab)

    def seeded_history(self, client: int, ordinal: int) -> List[int]:
        """History for a client whose first request lands mid-session."""
        t = self.turn(client, ordinal)
        n = t.prompt_tokens - self.system_tokens - t.user_tokens
        return tokens(rng_for(self.seed, 4, client), n, self.vocab)

    def lengths(self, requests_per_client: int) -> List[int]:
        """Prompt lengths of the first n requests of every client, sorted:
        the multiset two seeds must share."""
        return sorted(
            self.turn(c, n).prompt_tokens
            for c in range(self.clients) for n in range(requests_per_client)
        )

    def longest_context(self) -> int:
        span = self.turns * len(self.user_cycle)
        turns = [self.turn(0, n) for n in range(span)]
        return max(t.prompt_tokens + t.max_tokens for t in turns)


class ClientSession:
    """The prompt a client sends next, given what came back so far."""

    def __init__(self, plan: ClosedLoopPlan, client: int):
        self.plan = plan
        self.client = client
        self.ordinal = 0
        first = plan.turn(client, 0)
        self.context: List[int] = (
            list(plan.system_prompt) + plan.seeded_history(client, 0)
            if first.turn else list(plan.system_prompt)
        )

    def next_prompt(self) -> tuple:
        t = self.plan.turn(self.client, self.ordinal)
        if t.turn == 0:
            self.context = list(self.plan.system_prompt)
        self.context.extend(self.plan.user_message(self.client, self.ordinal))
        if len(self.context) != t.prompt_tokens:
            raise AssertionError(
                f"client {self.client} request {self.ordinal}: prompt of "
                f"{len(self.context)} tokens, the plan says {t.prompt_tokens}"
            )
        return t, list(self.context)

    def answered(self, answer: Sequence[int]) -> None:
        self.context.extend(int(a) for a in answer)
        self.ordinal += 1


# ---------------------------------------------------------------- lm steps


def lm_batches(spec: dict, seed: int, vocab: int) -> Iterator[Dict[str, np.ndarray]]:
    """Endless seeded (batch, seq + 1) int32 token batches. Ids follow a
    Zipf-like law (exponent `zipf_a`) so that a model has a unigram
    distribution to learn and the loss falls within a few steps; the
    first batch is the one the reference loss is taken on."""
    batch, seq = int(spec["batch"]), int(spec["seq"])
    a = float(spec.get("zipf_a", 1.1))
    ranks = np.arange(1, vocab + 1, dtype=np.float64)
    p = ranks ** -a
    p /= p.sum()
    cdf = np.cumsum(p)
    # a seeded permutation decides which ids are the frequent ones
    ids = rng_for(seed, 10).permutation(vocab).astype(np.int32)
    n = 0
    while True:
        u = rng_for(seed, 11, n).random(size=(batch, seq + 1))
        yield {"tokens": ids[np.minimum(np.searchsorted(cdf, u), vocab - 1)]}
        n += 1
