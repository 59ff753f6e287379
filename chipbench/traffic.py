"""Closed-loop session traffic, generated from a mix file and a seed.

A mix file (``chipbench/traffic/<mix>.json``) holds parameters only:

    loop               "closed": a session sends its next turn as soon as
                       its previous one is done (zero think time)
    sessions           number of concurrent sessions (the decode batch)
    prompt_tokens      {"values": [...], "weights": [...]}: discrete prompt
                       lengths (prefill compiles per exact length)
    answer_tokens      {"values": [...], "weights": [...]}: max_new per turn
    block_size         tokens per KV page
    blocks_per_arena   pages per arena; the pool is a whole number of arenas

Every seed gets the same work in another order: turn ``r`` of all sessions
draws its (prompt, answer) pair from one deck that holds each length in
proportion to its weight (largest remainder, at least once each), the
answers dealt round-robin over the sorted prompts; the seed shuffles the
pairs among the sessions.
So round 0, which fills the batch in set-up, holds every prompt length,
and the fill warms every prefill shape the window can use.
"""
from __future__ import annotations

import json
import math
from pathlib import Path
from typing import Dict, List, Tuple

import numpy as np


def load_mix(path: Path) -> Dict:
    mix = json.loads(Path(path).read_text())
    if mix.get("loop") != "closed":
        raise ValueError(f"{path}: only closed-loop session mixes are generated")
    for key in ("prompt_tokens", "answer_tokens"):
        d = mix[key]
        if len(d["values"]) != len(d["weights"]) or min(d["weights"]) <= 0:
            raise ValueError(f"{path}: {key} needs one positive weight per value")
    return mix


def deck(values: List[int], weights: List[float], n: int) -> List[int]:
    """``n`` lengths in proportion to ``weights``: largest remainder, with
    every value at least once where ``n`` allows."""
    w = np.asarray(weights, float) / float(np.sum(weights))
    exact = w * n
    counts = np.floor(exact).astype(int)
    if n >= len(values):
        counts = np.maximum(counts, 1)
    order = np.argsort(-(exact - np.floor(exact)), kind="stable")
    i = 0
    while counts.sum() < n:
        counts[order[i % len(values)]] += 1
        i += 1
    while counts.sum() > n:             # min-1 rule overshot: trim the largest
        counts[int(np.argmax(counts))] -= 1
    return [v for v, c in zip(values, counts) for _ in range(int(c))]


def pool_sizing(mix: Dict) -> Tuple[int, int]:
    """(num_blocks, max_blocks_per_seq): every session can hold its longest
    turn at once, rounded up to whole arenas, so no admission waits and no
    sequence is preempted."""
    bs, arena = mix["block_size"], mix["blocks_per_arena"]
    longest = max(mix["prompt_tokens"]["values"]) + max(mix["answer_tokens"]["values"])
    per_seq = math.ceil(longest / bs)
    return math.ceil(mix["sessions"] * per_seq / arena) * arena, per_seq


def seed_state(seed: int, stream: int) -> int:
    """A 32-bit seed for ``stream`` derived from any non-negative ``seed``."""
    return int(np.random.SeedSequence([seed, stream]).generate_state(1)[0])


class Sessions:
    """Turn generator: ``next_turn(session)`` gives (prompt ids, max_new)."""

    def __init__(self, mix: Dict, seed: int, vocab: int):
        self.mix = mix
        self.vocab = vocab
        self.n = mix["sessions"]
        self._seed = seed_state(seed, 7)
        self._rng = np.random.default_rng(self._seed)
        self._rounds: List[List[Tuple[int, int]]] = []
        self._turn = [0] * self.n
        p, a = mix["prompt_tokens"], mix["answer_tokens"]
        answers = deck(a["values"], a["weights"], self.n)
        by_value = [[x for x in answers if x == v] for v in a["values"]]
        dealt = [col.pop() for _ in range(self.n) for col in by_value if col][:self.n]
        self._pairs = list(zip(deck(p["values"], p["weights"], self.n), dealt))

    def _round(self, r: int) -> List[Tuple[int, int]]:
        while len(self._rounds) <= r:
            self._rounds.append([self._pairs[i] for i in self._rng.permutation(self.n)])
        return self._rounds[r]

    def next_turn(self, session: int) -> Tuple[List[int], int]:
        r = self._turn[session]
        self._turn[session] += 1
        plen, max_new = self._round(r)[session]
        rng = np.random.default_rng([self._seed, session, r])
        prompt = rng.integers(0, self.vocab, plen).tolist()
        return prompt, max_new
