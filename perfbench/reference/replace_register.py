"""The plain reference of a follower lost, evicted and replaced under a
load of SETs whose keys never repeat: a dict and a membership timeline.

What the group must hold at the end does not depend on the replacement:
every acknowledged ``SET`` is in EVERY member's app, the replaced one's
included, as if nothing had happened; each operation that was sent and
never acknowledged (``unresolved``) may or may not be there. So an app
is judged against the dict of what was acknowledged, key by key on a
seeded sample and by its count of keys.

The membership is three lines: everybody, everybody but the victim
from the eviction on, everybody again from ``AddServer`` on; and the
app that stands in the victim's place at the end is another process
than the one that was killed, and started with nothing.

Imports nothing of the program under test, nor of the harness.
"""

from __future__ import annotations

import random
from typing import Dict, List, Sequence

SAMPLE_KEYS = 200


def membership_timeline(members: Sequence[int], victim: int) -> List[tuple]:
    """(from which event on, who is a member): the three lines."""
    everybody = frozenset(members)
    return [("boot", everybody),
            ("evicted", everybody - {victim}),
            ("add_server", everybody)]


def members_after(events: Sequence[str], members: Sequence[int],
                  victim: int) -> frozenset:
    """Who has to be a member once ``events`` (names of the timeline's
    lines, in the order they happened) have happened."""
    now = frozenset(members)
    for name, who in membership_timeline(members, victim):
        if name == "boot" or name in events:
            now = who
    return now


def sample_keys(acked: Dict[bytes, bytes], seed: int,
                n: int = SAMPLE_KEYS) -> List[bytes]:
    return random.Random(f"sample:{seed}").sample(
        sorted(acked), min(n, len(acked)))


def count_limits(acked: Dict[bytes, bytes], unresolved: int) -> tuple:
    """(fewest, most) keys an app may hold."""
    return len(acked), len(acked) + unresolved


def wrong_values(acked: Dict[bytes, bytes], keys: Sequence[bytes],
                 answers: Sequence[bytes]) -> int:
    """Of ``keys``, how many an app answered otherwise than
    acknowledged."""
    return sum(1 for k, a in zip(keys, answers) if a != acked[k])


def replaced_app_faults(old_pid, new_pid, keys_at_start: int) -> int:
    """0 where the app in the victim's place is a new process that
    started empty (``keys_at_start`` -1: it could not be asked, and a
    new process of an app that keeps nothing on disk holds nothing);
    else what is wrong with it, counted."""
    return (int(new_pid is None or new_pid == old_pid)
            + int(keys_at_start > 0))
