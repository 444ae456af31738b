"""The bytes one dispatch has to move, from the log's shapes.

An operation is one log entry: its payload slot and its metadata row.
The least a replicated append can cost in device memory traffic is: the
staged batch is read once and written into the leader's ring, and each
of the other ``replicas - 1`` rings reads it from the leader's and
writes it into its own: ``2 * replicas`` passes over the entry. Control
state (terms, cursors, the quorum scan's window of acknowledgements) is
some hundreds of bytes per step and is left out, so the share of the
roofline this gives is a floor on how far the program is from it, not a
ceiling. The step is bound by memory bandwidth, not by arithmetic: it
adds and compares integers and multiplies nothing.
"""

from __future__ import annotations


def min_bytes_per_dispatch(ops_per_dispatch: float, replicas: int,
                           entry_bytes: int) -> float:
    return 2.0 * replicas * entry_bytes * ops_per_dispatch


def roofline_share_pct(bytes_moved: float, seconds: float,
                       peak_bytes_per_s: float, chips: int = 1) -> float:
    """Share of the memory roofline: the least time ``chips`` chips
    need to move ``bytes_moved`` between them, over the time taken."""
    return 100.0 * (bytes_moved / chips / peak_bytes_per_s) / seconds
