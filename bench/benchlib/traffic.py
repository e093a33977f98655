"""The one generator of requests, driven by a traffic file's parameters.

Lengths come in decks: each deck holds every listed value as often as its
count says, shuffled from the seed, so every seed serves the same mix of
sizes in another order. Prompt tokens are uniform over the vocabulary.
"""
from __future__ import annotations

import numpy as np


def rng_for(seed: int, stream: int = 0) -> np.random.Generator:
    """A generator for ``seed`` (any whole number) and a sub-stream."""
    return np.random.default_rng([seed % 2**63, stream])


class Deck:
    """An endless sequence of ``values``, each deck of sum(counts) items
    holding value i exactly counts[i] times, in the seed's order."""

    def __init__(self, values: list[int], counts: list[int],
                 rng: np.random.Generator) -> None:
        if len(values) != len(counts) or min(counts) < 1:
            raise ValueError(f"values {values} and counts {counts} do not pair up")
        self.values = sorted(values)
        self._deck = np.repeat(np.asarray(values), counts)
        self._rng = rng
        self._left: list[int] = []

    def next(self) -> int:
        if not self._left:
            self._left = list(self._rng.permutation(self._deck))
        return int(self._left.pop())


class RequestStream:
    """Requests in the order every run of this seed sends them.

    Traffic keys: ``prompt_lens`` and ``prompt_counts``; ``new_tokens`` and
    ``new_token_counts`` (or one ``new_tokens`` number); optional
    ``length_group`` (default 1): that many requests in a row share one
    prompt length, drawn from the deck once for the group."""

    def __init__(self, traffic: dict, vocab_size: int, seed: int) -> None:
        self._lens = Deck(traffic["prompt_lens"], traffic["prompt_counts"],
                          rng_for(seed, 1))
        nt = traffic["new_tokens"]
        if isinstance(nt, int):
            nt, counts = [nt], [1]
        else:
            counts = traffic["new_token_counts"]
        self._new = Deck(nt, counts, rng_for(seed, 2))
        self._tok = rng_for(seed, 3)
        self.vocab_size = vocab_size
        self._group = int(traffic.get("length_group", 1))
        self._sent = 0
        self._len = 0

    def next(self) -> tuple[np.ndarray, int]:
        if self._sent % self._group == 0:
            self._len = self._lens.next()
        self._sent += 1
        n = self._len
        prompt = self._tok.integers(0, self.vocab_size, size=n, dtype=np.int32)
        return prompt, self._new.next()

    def budgets(self) -> list[int]:
        return self._new.values

    def shapes(self) -> list[tuple[int, int]]:
        """Every (longest prompt, largest budget) a batch can have."""
        return [(t, b) for t in self._lens.values for b in self._new.values]
