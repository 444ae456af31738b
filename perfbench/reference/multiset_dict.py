"""The plain reference of the batched-write cells: a dict.

Independent of the program under test (it imports nothing of it): it is
fed the ACKNOWLEDGED requests in the order they were acknowledged, each
a list of ``(key, value)`` pairs, and holds what a sound store then
holds. No key is written twice and every value is unique, so the
answers are exact: an app holds ``len(ref)`` keys (and, where requests
were sent and never answered, whole requests more: each is in an app
whole or not at all), and answers ``ref[key]`` for every key of it.
"""

from __future__ import annotations

from typing import Dict, Iterable, Sequence, Tuple

Pairs = Sequence[Tuple[bytes, bytes]]


class MultisetDict:
    def __init__(self, pairs_a_request: int):
        self.pairs_a_request = pairs_a_request
        self.held: Dict[bytes, bytes] = {}
        self.requests = 0

    def multi_set(self, pairs: Pairs) -> None:
        if len(pairs) != self.pairs_a_request:
            raise ValueError(f"a request of {len(pairs)} pairs")
        for key, value in pairs:
            if key in self.held:
                raise ValueError(f"key written twice: {key!r}")
            self.held[key] = value
        self.requests += 1

    def feed(self, requests: Iterable[Pairs]) -> "MultisetDict":
        for pairs in requests:
            self.multi_set(pairs)
        return self

    def __len__(self) -> int:
        return len(self.held)

    def count_bounds(self, unresolved: int) -> Tuple[int, int]:
        """The fewest and the most keys an app may hold, given the
        requests sent and never acknowledged."""
        return (len(self.held),
                len(self.held) + self.pairs_a_request * unresolved)

    def part_held(self, count: int) -> int:
        """Of ``count`` keys, those beyond a whole number of requests:
        not 0 where a request is held in part."""
        return count % self.pairs_a_request

    def wrong_values(self, keys: Sequence[bytes],
                     answers: Sequence[bytes]) -> int:
        """Of ``keys``, those answered otherwise than held."""
        return sum(1 for k, got in zip(keys, answers)
                   if got != self.held.get(k))
