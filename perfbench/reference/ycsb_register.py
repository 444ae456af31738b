"""The plain reference of the YCSB cells: each field of each record is a
register, and a history of timed writes and reads says which values it
may hold.

Independent of the program under test (it imports nothing of it, and
shares no code with ``rdma_paxos_tpu/chaos/linearize.py``): it is handed
the generator's table of operations, every stamp taken by ONE process on
one monotonic clock, the request's before it is written and the reply's
after it is read, so an operation took effect somewhere inside its
``[t_req, t_rep]``. A write that was sent and never answered
(``UNRESOLVED``) may have taken effect at any time after its request, or
never. A write answered with an error did not happen and is not in the
table.

``W'`` *strictly follows* ``W`` when ``W'.t_req > W.t_rep``. Then:

(a) at the end, a field may hold the value of any write to it that no
    ACKNOWLEDGED write strictly follows (:meth:`Registers.admissible`);
(b) an acknowledged read ``R`` may have returned, for a field, the value
    of a write ``W`` that began before ``R``'s reply, unless an
    acknowledged write that finished before ``R``'s request strictly
    follows ``W``; it may have found the field absent only if no
    acknowledged write finished before its request
    (:meth:`Registers.read_admissible`).

Every written value is unique, so a value names its write and both
answers are exact: the limit on inadmissible values is 0.
"""

from __future__ import annotations

import bisect
import dataclasses
import math
from typing import Dict, Iterable, List, Optional, Set, Tuple

ACKED, UNRESOLVED = 1, 0
INF = math.inf


@dataclasses.dataclass(frozen=True)
class Write:
    key: bytes
    field: bytes
    value: bytes
    t_req: float
    t_rep: float            # INF where the write was never answered
    state: int              # ACKED or UNRESOLVED


@dataclasses.dataclass(frozen=True)
class Read:
    key: bytes
    fields: Optional[Dict[bytes, bytes]]    # None: the record was absent
    t_req: float
    t_rep: float


class _Register:
    """The writes to one field of one record."""

    def __init__(self):
        self.by_value: Dict[bytes, Write] = {}
        self.acked: List[Write] = []
        self._rep: List[float] = []         # acked, sorted by t_rep
        self._req_max: List[float] = []     # running max of their t_req

    def add(self, w: Write) -> None:
        if w.value in self.by_value:
            raise ValueError(f"value written twice: {w.value[:24]!r}")
        self.by_value[w.value] = w
        if w.state == ACKED:
            self.acked.append(w)

    def seal(self) -> None:
        self.acked.sort(key=lambda w: w.t_rep)
        self._rep = [w.t_rep for w in self.acked]
        top, self._req_max = -INF, []
        for w in self.acked:
            top = max(top, w.t_req)
            self._req_max.append(top)

    def latest_begin_finished_before(self, t: float) -> float:
        """max ``t_req`` over acknowledged writes with ``t_rep < t``."""
        n = bisect.bisect_left(self._rep, t)
        return self._req_max[n - 1] if n else -INF


class Registers:
    def __init__(self, writes: Iterable[Write]):
        self._regs: Dict[Tuple[bytes, bytes], _Register] = {}
        self.fields_of: Dict[bytes, Set[bytes]] = {}
        for w in writes:
            self._regs.setdefault((w.key, w.field), _Register()).add(w)
            self.fields_of.setdefault(w.key, set()).add(w.field)
        for reg in self._regs.values():
            reg.seal()

    # ---- (a) --------------------------------------------------------

    def admissible(self, key: bytes, field: bytes) -> Set[Optional[bytes]]:
        """The values the field may hold once everything has settled;
        ``None`` in the set: it may be absent."""
        reg = self._regs.get((key, field))
        if reg is None:
            return {None}
        last_begin = reg.latest_begin_finished_before(INF)
        out: Set[Optional[bytes]] = {
            w.value for w in reg.by_value.values() if w.t_rep >= last_begin}
        if not reg.acked:
            out.add(None)
        return out

    def record_faults(self, key: bytes,
                      got: Optional[Dict[bytes, bytes]]) -> List[str]:
        """What is wrong with a whole record as an app holds it at the
        end; empty: every field is admissible."""
        got = got or {}
        faults = []
        for field in sorted(self.fields_of.get(key, set()) | set(got)):
            if got.get(field) not in self.admissible(key, field):
                faults.append(f"{key.decode()}.{field.decode()}="
                              f"{_short(got.get(field))}")
        return faults

    def ambiguous_keys(self) -> int:
        """Records with a field that may end on more than one value."""
        return len({key for (key, field) in self._regs
                    if len(self.admissible(key, field)) > 1})

    # ---- (b) --------------------------------------------------------

    def read_admissible(self, key: bytes, field: bytes,
                        value: Optional[bytes], t_req: float,
                        t_rep: float) -> bool:
        reg = self._regs.get((key, field))
        if reg is None:
            return value is None
        overwritten_before = reg.latest_begin_finished_before(t_req)
        if value is None:
            return overwritten_before == -INF
        w = reg.by_value.get(value)
        if w is None or w.t_req >= t_rep:
            return False        # nobody wrote it, or not by then
        return not overwritten_before > w.t_rep

    def read_faults(self, r: Read) -> List[str]:
        got = r.fields or {}
        return [f"{r.key.decode()}.{field.decode()}="
                f"{_short(got.get(field))}"
                for field in sorted(self.fields_of.get(r.key, set())
                                    | set(got))
                if not self.read_admissible(r.key, field, got.get(field),
                                            r.t_req, r.t_rep)]

    def previous_version(self, r: Read) -> Optional[Dict[bytes, bytes]]:
        """The record ``r`` read, one acknowledged write earlier: with
        the last write that finished before ``r`` began undone (its
        field back at the newest value that write strictly follows,
        or the record absent where that was its first). For the control
        that shows (b) catches a stale read; ``r.fields`` itself where
        nothing finished before ``r``."""
        last: Optional[Write] = None
        for field in self.fields_of.get(r.key, ()):
            reg = self._regs[(r.key, field)]
            n = bisect.bisect_left(reg._rep, r.t_req)
            if n and (last is None or reg.acked[n - 1].t_rep > last.t_rep):
                last = reg.acked[n - 1]
        if last is None:
            return r.fields
        reg = self._regs[(r.key, last.field)]
        older = [w for w in reg.acked if w.t_rep < last.t_req]
        if not older:
            return None
        out = dict(r.fields or {})
        out[last.field] = max(older, key=lambda w: w.t_rep).value
        return out


def _short(value: Optional[bytes]) -> str:
    return "absent" if value is None else value[:20].decode("ascii",
                                                            "replace")


def parse_record(line: bytes) -> Optional[Dict[bytes, bytes]]:
    """The one-line ``HGETALL`` answer ``f v f v ...``; ``-``: absent."""
    if line == b"-":
        return None
    parts = line.split(b" ")
    if len(parts) % 2:
        raise ValueError(f"odd record line: {line[:60]!r}")
    return dict(zip(parts[::2], parts[1::2]))
