"""``ycsb_register``'s reference for a keyspace split over replication
groups: what is new there, and nothing else.

A record belongs to ONE group (the table ``group_of``, key -> group, is
handed over by the caller: the deployment's own routing, which this
file neither knows nor re-implements), and the groups order their
writes independently of one another. So the registers are kept by
group: a write is filed under its key's group, a record's or a read's
admissibility (``ycsb_register``'s (a) and (b), unchanged) is asked of
that group's registers alone, and a record that the table does not
hold belongs to no group and is a fault by itself. What the apps must
hold is then asked of EVERY app for EVERY group, since every app leads
one group and follows the others: ``app_faults`` files an app's faults
by the group of the record they are on, so that a replica that misses
one group's replayed writes shows under that group and no other.

The per-group record counts (``records_per_group``) sum to the number
of records of the table; an app's ``COUNT`` is that sum.

Like ``ycsb_register`` it imports nothing of the program under test.
"""

from __future__ import annotations

from typing import Dict, Iterable, List, Optional

from perfbench.reference import ycsb_register as ref


class ClusterRegisters:
    def __init__(self, writes: Iterable[ref.Write],
                 group_of: Dict[bytes, int], n_groups: int):
        self.group_of, self.n_groups = group_of, n_groups
        by_group: List[list] = [[] for _ in range(n_groups)]
        self.strays = []        # writes to a key the table does not hold
        for w in writes:
            g = group_of.get(w.key)
            (self.strays if g is None else by_group[g]).append(w)
        self.groups = [ref.Registers(ws) for ws in by_group]

    def records_per_group(self) -> List[int]:
        out = [0] * self.n_groups
        for g in self.group_of.values():
            out[g] += 1
        return out

    def _of(self, key: bytes) -> Optional[ref.Registers]:
        g = self.group_of.get(key)
        return None if g is None else self.groups[g]

    # ---- (a), by group ----------------------------------------------

    def record_faults(self, key: bytes,
                      got: Optional[Dict[bytes, bytes]]) -> List[str]:
        regs = self._of(key)
        if regs is None:
            return [f"{key.decode()}: in no group"]
        return regs.record_faults(key, got)

    def app_faults(self, records: Dict[bytes, Optional[Dict[bytes, bytes]]]
                   ) -> List[List[str]]:
        """One app's records (key -> parsed ``HGETALL``) -> its faults,
        filed by the group of the record each is on."""
        out: List[List[str]] = [[] for _ in range(self.n_groups)]
        for key, got in records.items():
            g = self.group_of.get(key)
            out[0 if g is None else g].extend(self.record_faults(key, got))
        return out

    def ambiguous_keys(self) -> int:
        return sum(regs.ambiguous_keys() for regs in self.groups)

    # ---- (b), by group ----------------------------------------------

    def read_faults(self, r: ref.Read) -> List[str]:
        regs = self._of(r.key)
        if regs is None:
            return [f"{r.key.decode()}: in no group"]
        return regs.read_faults(r)

    def previous_version(self, r: ref.Read):
        return self._of(r.key).previous_version(r)
