"""The plain reference of the pipelined SET cell: each key is a register
that several connections write, and the history of timed writes says
which values it may hold at the end.

Independent of the program under test (it imports nothing of it): a
dict, and the generator's stamps, every one taken by ONE process on one
monotonic clock, a request's before its batch is written and its
reply's after it is read, so a write took effect somewhere inside its
``[t_req, t_rep]``. A write that was sent and never answered
(``UNRESOLVED``) may have taken effect at any time after its request, or
never. A write answered with an error did not happen and is not handed
over. The rule is ``ycsb_register.py``'s (a), for whole values:

``W'`` *strictly follows* ``W`` when ``W'.t_req > W.t_rep``. At the end
a key may hold the value of any write to it that no ACKNOWLEDGED write
strictly follows; it may be absent only if no write to it was
acknowledged. Two connections that write one key at once are both
admissible: the log's order decides, and every replica must have
decided alike, which is the caller's second question
(``apps_differ``).

Values are three letters, so two writes of one key may carry the same
value: the set of admissible VALUES is what is compared, and it is
exact either way (limit 0).
"""

from __future__ import annotations

import math
from typing import Dict, Iterable, List, Optional, Set, Tuple

ACKED, UNRESOLVED = 1, 0
INF = math.inf

# (key, value, t_req, t_rep, state); t_rep is ignored where UNRESOLVED
Write = Tuple[object, bytes, float, float, int]


class SetRegister:
    def __init__(self, writes: Iterable[Write],
                 only: Optional[Set[object]] = None):
        """``only``: the keys whose histories are kept (those that will
        be asked about); every key is counted."""
        self._by_key: Dict[object, List[Tuple[bytes, float, float]]] = {}
        self._acked_keys: Set[object] = set()
        self._maybe_keys: Set[object] = set()
        for key, value, t_req, t_rep, state in writes:
            if state == ACKED:
                self._acked_keys.add(key)
            else:
                self._maybe_keys.add(key)
                t_rep = INF
            if only is None or key in only:
                self._by_key.setdefault(key, []).append(
                    (value, t_req, t_rep))
        self._maybe_keys -= self._acked_keys

    def count_bounds(self) -> Tuple[int, int]:
        """-> (least, most) keys an app may hold: those with an
        acknowledged write, and at most those only ever written
        unresolved more."""
        least = len(self._acked_keys)
        return least, least + len(self._maybe_keys)

    def admissible(self, key) -> Set[Optional[bytes]]:
        """The values ``key`` may hold once everything has settled;
        ``None`` in the set: it may be absent."""
        writes = self._by_key.get(key, [])
        last_begin = max((t_req for _v, t_req, t_rep in writes
                          if t_rep != INF), default=-INF)
        out: Set[Optional[bytes]] = {
            value for value, _t_req, t_rep in writes if t_rep >= last_begin}
        if last_begin == -INF:
            out.add(None)
        return out

    def faults(self, keys: List[object],
               got: List[Optional[bytes]]) -> List[str]:
        """What an app holds for ``keys`` that the history does not
        admit; empty: every value is admissible."""
        out = []
        for key, g in zip(keys, got):
            may = self.admissible(key)
            if g not in may:
                out.append(f"{key}={_show(g)} (admissible: "
                           f"{sorted(map(_show, may))})")
        return out

    def ambiguous(self, keys: Iterable[object]) -> int:
        """Of ``keys``, those that may end on more than one value."""
        return sum(1 for key in keys if len(self.admissible(key)) > 1)


def _show(value: Optional[bytes]) -> str:
    return "absent" if value is None else value.decode("ascii", "replace")


def apps_differ(answers: List[List[Optional[bytes]]]) -> int:
    """Of the keys every app was asked, those they do not hold alike."""
    return sum(1 for per_key in zip(*answers) if len(set(per_key)) > 1)
