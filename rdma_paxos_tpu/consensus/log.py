"""The replicated log as fixed-shape on-device arrays.

Reference: the DARE log is a byte-granular 64 MB circular buffer, remotely
writable via one-sided RDMA, with four offsets ``head/apply/commit/end`` and
entry framing ``{idx, term, req_id, clt_id, type, reply[], data}``
(``src/include/dare/dare_log.h:33-47,76-103``) plus wrap-around splitting
rules (``dare_log.h:466-558``).

TPU-native redesign (NOT a translation):

* **Slot-based ring, one fused row a slot.** Fixed-size slots; a slot is
  ONE ``int32`` row of ONE ``[n_slots, row_words]`` array: ``slot_words``
  of payload, then the ``META_W`` columns of framing metadata, then zero
  padding up to the next multiple of 128 words (:func:`row_words`; the
  reference packs variable-size structs into a byte buffer). One array,
  so that every ring gather / scatter of the hot path touches one; a
  multiple of the TPU's 128 lanes, so that row-major is the layout the
  runtime RESTS the ring in and the step, which reads and writes rows,
  compiles no layout copy of it (two of them, each over the whole ring,
  were 60% of the step at 136 columns: PERF.md section 6, PR 50). The
  pad is on the device only: whatever leaves it (the replay fetch, a
  snapshot's or an exported row's ``log_buf``, a digest) carries the
  LIVE columns, ``slot_words + META_W`` (:func:`live_rows`), and
  :func:`pad_rows` puts the pad back at install. Oversize payloads are
  fragmented by the proxy into consecutive SEND entries, which is
  semantically lossless for stream replay.
* **Global monotone indices.** ``head/apply/commit/end`` are monotonically
  increasing int32 *entry* indices; the slot of global index ``g`` is
  ``g % n_slots``. The reference's wrap-around entry-splitting machinery
  (``dare_log.h:496-545``) disappears: wrap is a single cheap mask, and the
  two-segment RDMA write on wrap (``dare_ibv_rc.c:1539-1545``) becomes a
  gather/scatter with modular indices.
* **No reply[] array in the entry.** The reference embeds a per-entry ACK
  byte-array that followers RDMA-write into the leader's log
  (``dare_log.h:44``). On TPU, acknowledgement is an ``all_gather`` of
  follower ``end`` offsets (see ``consensus/step.py``) — the per-entry ACK
  bitmap materializes only inside the quorum kernel (``ops/quorum.py``).

Everything here is pure and shape-static: callable under ``jit``, ``vmap``
and ``shard_map``.
"""

from __future__ import annotations

import dataclasses
import enum
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from rdma_paxos_tpu.config import LogConfig


class EntryType(enum.IntEnum):
    """Log entry types — reference ``dare_log.h:22-25`` (NOOP/CSM/CONFIG)
    plus proxy event types carried in CSM entries (CONNECT/SEND/CLOSE,
    reference ``src/include/dare/message.h``).

    The reference's fourth type, HEAD (``dare_log.h:25`` — a durable log
    entry publishing the pruned head offset, ``log_pruning``
    ``dare_server.c:1996-2067``), has NO analog here by design: the head
    offset rides EVERY leader window message as a scalar column
    (``S_HEAD``, consensus/step.py Phase D/E), so followers learn head
    advancement continuously instead of through an in-log record, and a
    restarted replica recovers head from its snapshot determinant
    (consensus/snapshot.py). A durable in-log HEAD entry would be
    redundant state with no consumer."""

    EMPTY = 0       # unwritten slot
    NOOP = 1        # blank entry appended by a fresh leader (dare_server.c:1487)
    CONNECT = 2     # proxy: new client connection     (proxy.c:163-228)
    SEND = 3        # proxy: client payload bytes      (proxy.c:230-239)
    CLOSE = 4       # proxy: connection closed         (proxy.c:241-261)
    CONFIG = 5      # membership change                (dare_log.h:24)


# Metadata columns (SoA): meta[slot, col]. M_GIDX is the entry's global
# monotone index, stamped at append time — it lets a full-ring scan
# reconstruct which slots are live ([head, end)) without walking offsets,
# e.g. the CONFIG-derivation scan in consensus/step.py. A recycled slot's
# stale gidx is always < head (the ring holds <= n_slots live entries), so
# `gidx >= head` alone identifies liveness.
#
# DESIGN CONSTRAINT: all log offsets (head/apply/commit/end and M_GIDX)
# are i32 entry indices, so a deployment is bounded at 2^31-1 entries
# (~13 minutes at the benched multi-M ops/s). The epoch-rebase path
# already exists: snapshot install renumbers offsets from the snapshot
# index (consensus/snapshot.py), so a long-running cluster rolls over by
# a coordinated snapshot+install well before the ceiling — the same
# mechanism a joiner uses. The reference has the analogous bound in its
# uint64 byte offsets (dare_log.h:77-103), just further away.
M_TYPE, M_TERM, M_CONN, M_REQID, M_LEN, M_GIDX = 0, 1, 2, 3, 4, 5
# M_GEN: the elastic generation of the submitting host incarnation —
# lets a rebuilt host distinguish entries ITS CURRENT app served live
# (gen matches: ack, don't replay) from entries a previous incarnation
# originated (gen differs: replay into the rebuilt app like any remote
# entry). An explicit column, not high bits of req_id, so neither
# counter can overflow into misclassification.
M_GEN = 6
META_W = 8  # padded for alignment
# a ring row is padded to a multiple of the TPU's lane count, in words
ROW_ALIGN = 128


def row_words(slot_words: int) -> int:
    """Width of a ring row: ``slot_words + META_W`` rounded UP to a
    multiple of :data:`ROW_ALIGN` (nothing is added where it already
    is one)."""
    return -(-(slot_words + META_W) // ROW_ALIGN) * ROW_ALIGN


def live_slot_words(rows) -> int:
    """``slot_words`` of rows in the LIVE format (``[..., slot_words +
    META_W]``: fetched windows, digest inputs, a ``log_buf`` on the
    host). A ring row's own width says nothing of it: ask the
    :class:`Log`."""
    return rows.shape[-1] - META_W


def live_rows(rows, slot_words: int):
    """The live columns (payload, then metadata) of rows read out of
    the ring: taken from what was gathered, never from the ring."""
    return rows[..., :slot_words + META_W]


def pad_rows(live: np.ndarray) -> np.ndarray:
    """Host rows in the live format, zero-padded to the ring's width:
    what installs a ``log_buf`` that travelled without its pad."""
    pad = -live.shape[-1] % ROW_ALIGN
    if not pad:
        return live
    return np.pad(live, [(0, 0)] * (live.ndim - 1) + [(0, pad)])


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class Log:
    """Per-replica log: one ``[n_slots, row_words(slot_words)]`` array,
    a row ``[payload | metadata | zero pad]`` (module docstring).

    Device code reads ROWS FIRST (:func:`rows_at`, :func:`extract_window`,
    :func:`window_rows`: index ``buf`` by slot, then take the columns of
    what was gathered) and there is no column view of the ring to reach
    for: on the v5e such a view is not fused into the gather that
    follows it, it materialises ``[n_slots, columns]`` for every replica
    (four of them were half of a 9.6 ms dispatch; PERF.md section 6, PR
    30). The one reader of every slot, the step's config rescan, which
    runs only where a cached config source was invalidated, has
    :func:`ring_meta`.

    ``slot_words`` is static (part of the tree's structure, not a
    leaf): the padded width does not say where the metadata starts."""

    buf: jax.Array    # [..., n_slots, row_words(slot_words)] int32
    slot_words: int = dataclasses.field(metadata=dict(static=True))

    # axis-agnostic: a single replica's [n_slots, cols] buf or batched
    # [R, n_slots, cols] state (vmap/stacked)
    @property
    def n_slots(self) -> int:
        return self.buf.shape[-2]


def make_log(cfg: LogConfig) -> Log:
    return Log(buf=jnp.zeros((cfg.n_slots, row_words(cfg.slot_words)),
                             jnp.int32),
               slot_words=cfg.slot_words)


def _fuse(data: jax.Array, meta: jax.Array) -> jax.Array:
    """``[N, slot_words]`` and ``[N, META_W]`` as ring rows, the pad
    written as zeros with them (so it stays zero)."""
    pad = row_words(data.shape[-1]) - data.shape[-1] - META_W
    parts = [data, meta]
    if pad:
        parts.append(jnp.zeros(data.shape[:-1] + (pad,), data.dtype))
    return jnp.concatenate(parts, axis=-1)


def _split(rows: jax.Array, slot_words: int):
    """``(data, meta)`` of rows gathered from the ring."""
    return (rows[..., :slot_words],
            rows[..., slot_words:slot_words + META_W])


def ring_meta(log: Log) -> jax.Array:
    """The metadata columns of EVERY slot, ``[n_slots, META_W]``: a
    ring-sized read, for the config rescan's taken branch alone."""
    return _split(log.buf, log.slot_words)[1]


def slot_of(g: jax.Array, n_slots: int) -> jax.Array:
    """Slot index of global entry index ``g`` (n_slots is a power of two)."""
    return jnp.bitwise_and(g, n_slots - 1)


def rows_at(log: Log, g: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """``(data, meta)`` of the entries at global indices ``g`` (a scalar
    or ``[N]``): ONE gather of ``buf`` by row, the columns taken from
    what was gathered — O(rows read) of the ring, never a view of it.
    The read every device-side caller goes through."""
    return _split(log.buf[slot_of(g, log.n_slots)], log.slot_words)


def last_term(log: Log, end: jax.Array) -> jax.Array:
    """Term of the last entry of a log that ends at ``end``, i.e. of entry
    ``end - 1`` (0 for an empty log) — the election up-to-date check
    (reference ``dare_server.c:1596-1652``) and the AppendEntries
    prev-term of a window that starts at ``end``."""
    _, m = rows_at(log, end - 1)
    return jnp.where(end > 0, m[M_TERM], 0)


# ---------------------------------------------------------------------------
# Append (leader)
# ---------------------------------------------------------------------------

def append_batch(
    log: Log,
    end: jax.Array,
    head: jax.Array,
    batch_data: jax.Array,   # [B, slot_words] int32
    batch_meta: jax.Array,   # [B, META_W] int32 (M_TERM overwritten here)
    count: jax.Array,        # scalar int32, entries actually present (<= B)
    term: jax.Array,         # scalar int32, leader's current term
) -> Tuple[Log, jax.Array]:
    """Append up to ``count`` entries at ``end`` stamped with ``term``.

    The capacity clamp enforces the reference's invariant that appends never
    overtake ``head`` (``log_append_entry``'s free-space check,
    ``dare_log.h:466-558``); entries that do not fit are dropped here and the
    proxy retries them next step (the reference instead forces log pruning,
    ``dare_server.c:2069-2122`` — our host driver does the same by feeding
    apply offsets forward, see ``consensus/step.py``).

    Returns ``(log', new_end)``.
    """
    n_slots = log.n_slots
    B = batch_data.shape[0]
    # Capacity is n_slots-1 (one slot always kept free) so that for any
    # window start >= head, slot(wstart-1) still physically holds entry
    # wstart-1 — the AppendEntries prev-term check in the step never reads
    # a recycled slot.
    avail = (n_slots - 1) - (end - head)
    n = jnp.clip(jnp.minimum(count, avail), 0, B).astype(jnp.int32)

    offs = jnp.arange(B, dtype=jnp.int32)
    valid = offs < n
    # out-of-range index => dropped by scatter mode="drop"
    idx = jnp.where(valid, slot_of(end + offs, n_slots), n_slots)

    meta = batch_meta.at[:, M_TERM].set(term)
    meta = meta.at[:, M_GIDX].set(end + offs)
    new_buf = log.buf.at[idx].set(_fuse(batch_data, meta), mode="drop")
    return dataclasses.replace(log, buf=new_buf), end + n


# ---------------------------------------------------------------------------
# Window extract (leader fan-out) / absorb (follower accept)
# ---------------------------------------------------------------------------

def extract_window(
    log: Log, start: jax.Array, window_slots: int
) -> Tuple[jax.Array, jax.Array]:
    """Gather ``window_slots`` consecutive entries beginning at global index
    ``start`` into dense ``[W, ...]`` arrays.

    This is the replication payload the leader broadcasts — the analog of the
    RDMA WRITE of ``log[remote_end : end]`` (reference
    ``dare_ibv_rc.c:1526-1642``); the ring wrap that costs the reference two
    RDMA sends (``:1539-1545``) is absorbed by the modular gather.
    """
    return rows_at(log, start + jnp.arange(window_slots, dtype=jnp.int32))


def window_rows(
    log: Log, start: jax.Array, window_slots: int
) -> jax.Array:
    """:func:`extract_window`'s rows, bit for bit, FUSED and in the
    LIVE format (``[W, slot_words + META_W]``: the pad stays on the
    device) and read as SLICES: a window is contiguous in the ring but
    for one wrap, so it is the ``window_slots`` slots from ``start``'s
    (or, within a window of the ring's end, the ring's last
    ``window_slots``), then the ring's first ``window_slots`` where it
    wraps, and of the two laid end to end the ``window_slots`` rows
    from ``start``'s place among them. For a reader OUTSIDE the step
    (the replay fetch): whole rows are sliced out of the ring where it
    lies, whatever layout the device rests it in (a gather by row made
    the v5e convert the whole ring first while it rested slot-minor:
    PERF.md section 6, PR 48), and the live columns are taken from the
    rows that were sliced."""
    n_slots, W = log.n_slots, window_slots
    s = slot_of(start, n_slots)
    at = jnp.minimum(s, n_slots - W)
    cols = log.buf.shape[-1]
    tail = jax.lax.dynamic_slice(log.buf, (at, 0), (W, cols))
    both = jnp.concatenate([tail, log.buf[:W]], axis=0)
    return live_rows(jax.lax.dynamic_slice(both, (s - at, 0), (W, cols)),
                     log.slot_words)


def absorb_window(
    log: Log,
    my_end: jax.Array,
    wdata: jax.Array,     # [W, slot_words]
    wmeta: jax.Array,     # [W, META_W]
    wstart: jax.Array,    # global index of window[0]
    wcount: jax.Array,    # valid entries in the window
) -> Tuple[Log, jax.Array]:
    """Follower-side accept: merge a leader window into the local log.

    Implements the log-adjustment semantics of the reference
    (``log_adjustment`` steps LR_GET_WRITE→…→SET_END,
    ``dare_ibv_rc.c:1292-1451``; NC-buffer determinants,
    ``dare_log.h:58-65,339-359``) as pure data flow:

    * **Gap gate**: if ``wstart > my_end`` the follower cannot verify
      continuity and ignores the window (it will be covered next step, since
      the leader floors the window at the minimum active ``end``).
    * **Divergence truncation**: in the overlap ``[wstart, min(my_end,
      wend))`` compare per-entry terms; at the first mismatch the local
      suffix is stale (uncommitted entries of a deposed leader) and is
      discarded — the window contents replace it. With no mismatch a shorter
      window never truncates a longer log.
    * **Copy**: all valid window entries are scattered in (overwriting
      matching prefixes with identical bytes is a no-op).

    Term gating (stale-leader fencing — the analog of the QP revoke fencing,
    ``rc_revoke_log_access`` ``dare_ibv_rc.c:2156-2255``) happens in the
    caller (``consensus/step.py``): a window stamped with an old term never
    reaches this function.

    Returns ``(log', new_end)``.
    """
    n_slots = log.n_slots
    W = wdata.shape[0]
    offs = jnp.arange(W, dtype=jnp.int32)
    g = wstart + offs                       # global index per window position
    valid = offs < wcount
    wend = wstart + wcount

    accept = wstart <= my_end

    # --- divergence scan over the overlap ---
    local_terms = rows_at(log, g)[1][:, M_TERM]
    in_overlap = valid & (g < my_end)
    mismatch = in_overlap & (local_terms != wmeta[:, M_TERM])
    any_conflict = jnp.any(mismatch)

    # --- scatter the window in (one fused scatter) ---
    do_copy = valid & accept
    idx = jnp.where(do_copy, slot_of(g, n_slots), n_slots)
    new_buf = log.buf.at[idx].set(_fuse(wdata, wmeta), mode="drop")

    new_end = jnp.where(
        accept,
        jnp.where(any_conflict, wend, jnp.maximum(my_end, wend)),
        my_end,
    ).astype(jnp.int32)
    return dataclasses.replace(log, buf=new_buf), new_end
