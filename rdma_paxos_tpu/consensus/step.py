"""The SPMD replica step — the entire DARE protocol as ONE collective program.

The reference drives consensus from a libev event loop (``polling()``,
``src/dare/dare_server.c:1004-1125``) issuing one-sided RDMA verbs per peer:
log adjustment (``dare_ibv_rc.c:1292-1451``), log-delta writes
(``:1465-1826``), per-entry ACK replies (``:1828-1863``), vote requests
(``:969-1043``), heartbeats (``:868-912``), QP-reset fencing
(``:2156-2255``). Followers' CPUs are passive in the replication hot path.

TPU-native redesign: all replicas advance in lock-step through a single
jitted SPMD step over a 1-D ``replica`` mesh axis (one replica per chip).
Every asymmetric, per-peer interaction of the reference becomes *data* inside
a uniform program (SURVEY.md §7 "model follower lag as data"):

=====================================  =======================================
reference mechanism                     TPU-native equivalent (here)
=====================================  =======================================
RDMA WRITE of log delta per follower   leader window ``all_gather`` + local
(``update_remote_logs``)               term-gated ``absorb_window``
log adjustment / NC determinants       prev-term consistency check + data-
(``log_adjustment``)                   driven end backoff (AppendEntries rule)
per-entry ACK reply[] bytes            ``all_gather`` of verified match
(``rc_send_entries_reply``)            offsets (acks)
commit scan + majority count           ``ops.quorum.commit_scan`` (Pallas)
(``dare_ibv_rc.c:1725-1758``)
lazy commit push to followers          leader commit scalar rides the window
(``:1760-1819``)                       message (one-step lazy, like the ref)
HB RDMA write of SID into hb[]         window message with wcount==0
(``rc_send_hb``)                       (term+commit are the heartbeat)
QP RESET fencing of deposed leaders    term gating: a stale leader's window
(``rc_revoke_log_access``)             is never selected (dominant-leader
                                       rule) and never absorbed (term gate)
vote request / vote ack RDMA writes    one-round election: candidacy in the
(``rc_send_vote_request/_ack``)        control gather, votes in a second
                                       gather, winner derived locally
per-follower LR step state machines    none needed — lock-step; laggards are
(``handle_lr_work_completion``)        expressed by window flooring + acks
dual-quorum transitional configs       dual bitmask quorum in vote counting
(``dare_ibv_rc.c:2799-2957``)          and in the commit kernel
log pruning via remote apply offsets   min-of-applies head advance riding the
(``dare_server.c:1976-2122``)          control gather + window message
=====================================  =======================================

Failure semantics: ``peer_mask`` is each replica's local view of which peers
are reachable. On a real slice all-ones (an ICI chip failure kills the whole
SPMD program and is handled by the host layer: mesh rebuild + recovery from
stable storage). In simulation the mask models partitions/crashes exactly —
gathered rows from unheard peers are ignored, so a partitioned stale leader
can keep appending locally but can neither replicate nor commit (it lacks a
quorum), and steps down the moment it hears a higher term.

Collective cost per step: 3 small ``all_gather`` (control, votes, acks) + 1
window ``all_gather`` (W·slot_bytes per contributor). The window gather is
deliberately an all_gather rather than a masked ``psum`` so that split-brain
double-contribution cannot corrupt the payload — receivers *select* the
dominant leader's row.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from rdma_paxos_tpu.config import LogConfig
from rdma_paxos_tpu.consensus.log import (
    EntryType, Log, M_GIDX, M_TERM, M_TYPE, META_W,
    append_batch, absorb_window, extract_window, last_term, live_rows,
    live_slot_words, ring_meta, rows_at, slot_of, window_rows,
)
from rdma_paxos_tpu.consensus.state import ConfigState, ReplicaState, Role
from rdma_paxos_tpu.ops.quorum import R_PAD, commit_scan

I32_MIN = jnp.iinfo(jnp.int32).min
I32_MAX = jnp.iinfo(jnp.int32).max

# telemetry counter-vector columns (``telemetry=True`` steps emit one
# u32 vector per replica per step; the host-side consumer is
# obs/device.py, which mirrors this layout — this module must NOT
# import obs, so the two are pinned against each other by
# tests/test_device_obs.py instead). Counters are per-step counts the
# host accumulates; the last two columns are point-in-time gauges.
(T_ELECTIONS, T_VOTES_GRANTED, T_VOTES_DENIED, T_ACCEPTED,
 T_COMMITTED, T_UNHEARD, T_QUORUM_W, T_HEADROOM, T_N) = range(9)

# control-gather columns (C_VTERM/C_VFOR carry each replica's durable vote
# pair so vote records refresh on EVERY step — full or stable — not only
# through the election-phase vote gather; C_QDEP carries each host's
# submit backlog so every host derives the SAME burst-size hint — the
# collective-count coordination that lets multihost drivers dispatch
# fused multi-step bursts without an extra gather)
(C_TERM, C_ROLE, C_END, C_COMMIT, C_LTERM, C_APPLY, C_TMO,
 C_VTERM, C_VFOR, C_QDEP, C_HEAD, C_N) = range(12)
# window-message scalar columns
S_VALID, S_WSTART, S_WCOUNT, S_TERM, S_PREV, S_COMMIT, S_HEAD, S_N = range(8)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class StepInput:
    """Per-replica host→device inputs for one step."""

    batch_data: jax.Array    # [B, slot_words] i32 — client entries (leader)
    batch_meta: jax.Array    # [B, META_W] i32
    batch_count: jax.Array   # i32 — valid entries in the batch
    timeout_fired: jax.Array  # i32 — host election timer expired
    peer_mask: jax.Array     # [R] i32 — which peers this replica can hear
    apply_done: jax.Array    # i32 — host's applied index (echo)
    queue_depth: jax.Array   # i32 — host submit backlog beyond this batch
                             #   (rides the control gather; feeds the
                             #   burst-size hint every host computes
                             #   identically)
    # --- cross-group transaction commit lane (txn=True only) ---
    # None in the default program: None leaves add no pytree nodes, so
    # txn=False inputs (and programs) are BYTE-IDENTICAL to the
    # pre-txn step (cache-key guarded by tests/test_txn.py). The watch
    # is this group's outstanding PREPARE entry in LOG-OFFSET domain
    # (the host subtracts its rebase total); -1 = no watch armed.
    txn_watch: Optional[jax.Array] = None   # i32 — prepare log offset
    txn_term: Optional[jax.Array] = None    # i32 — term it was appended in


# the packed argument's minor axis, the chip's 128 lanes, and the rows
# of one (8, 128) tile of its resting layout: a replica's rows are
# whole tiles
ARG_LANES = 128
ARG_TILE_ROWS = 8


@dataclasses.dataclass(frozen=True)
class ArgLayout:
    """Where a dispatch's host inputs lie in its ONE packed argument:
    an i32 array ``[*lead, rows, 128]`` whose leading axes are the
    mesh's (``[R, rows, 128]``; the sharded engine's ``[G, R, rows,
    128]``), so that one sharding covers it and replica r's words go
    to chip r whole. A replica's ``rows x 128`` words hold, row-major,
    K steps' batches and the small words: each field of
    :attr:`fields` is ``(name, offset, shape)`` in words, the tail a
    zero pad to whole (8, 128) tiles. The minor axis is the lanes', so the array rests on the
    chip in the host's own word order (a transfer is a copy, and at
    ``slot_words`` 128 the batch is a reshape of whole rows). The host
    side (the staging buffers ARE views of the packed array:
    :meth:`views`) and the programs (:meth:`split` /
    :meth:`step_input`, in the trace) both read this; nothing else
    knows an offset. Made by :func:`arg_layout`."""

    K: int
    fields: Tuple[Tuple[str, int, Tuple[int, ...]], ...]
    rows: int

    def shape(self, lead: Tuple[int, ...] = ()) -> Tuple[int, ...]:
        return tuple(lead) + (self.rows, ARG_LANES)

    def split(self, packed) -> Dict[str, jax.Array]:
        """``{name: the field's words as [*lead, *shape]}``: static
        slices and reshapes, of a numpy array (views of it) as of a
        traced one. A field takes the rows that hold it; one that
        starts and ends on a row's edge is reshaped as it lies."""
        lead = packed.shape[:-2]
        parts = {}
        for name, off, shape in self.fields:
            n = int(np.prod(shape))
            first, last = off // ARG_LANES, -(-(off + n) // ARG_LANES)
            words = packed[..., first:last, :]
            if n != (last - first) * ARG_LANES:
                lo = off - first * ARG_LANES
                words = words.reshape(lead + (-1,))[..., lo:lo + n]
            parts[name] = words.reshape(lead + shape)
        return parts

    def views(self, packed: np.ndarray) -> Dict[str, np.ndarray]:
        """The host's way in: :meth:`split` with K moved to the front
        of the batch fields (``data [K, *lead, B, slot_words]``,
        ``meta``, ``count [K, *lead]``), the shapes the engines pack
        by. Views, all of them: a write lands in ``packed``."""
        lead = packed.ndim - 2
        parts = self.split(packed)
        for name in ("data", "meta", "count"):
            parts[name] = np.moveaxis(parts[name], lead, 0)
        assert all(np.may_share_memory(v, packed) for v in parts.values())
        return parts

    def idle(self, lead: Tuple[int, ...] = (), peer_mask=1) -> np.ndarray:
        """A packed argument that asks nothing: no entry, no timer,
        ``peer_mask`` heard (everyone: an all-zero mask is a deaf
        replica, not an idle one), no watch armed."""
        packed = np.zeros(self.shape(lead), np.int32)
        parts = self.split(packed)
        parts["peer_mask"][:] = peer_mask
        if "txn_watch" in parts:
            parts["txn_watch"][:] = -1
        return packed

    def step_input(self, parts: Dict[str, jax.Array], k,
                   timeout_fired=None) -> StepInput:
        """The :class:`StepInput` of step ``k`` (a traced index in a
        fused program's loop, 0 in a single step) out of
        :meth:`split`'s parts: the batch read in place, no transposed
        copy of the K stack. ``timeout_fired`` replaces the row's own
        word (a fused program fires no timer)."""
        lead = parts["applied"].ndim

        def at(x):
            return lax.dynamic_index_in_dim(x, k, axis=lead,
                                            keepdims=False)
        return StepInput(
            batch_data=at(parts["data"]), batch_meta=at(parts["meta"]),
            batch_count=at(parts["count"]),
            timeout_fired=(parts["timeout"] if timeout_fired is None
                           else timeout_fired),
            peer_mask=parts["peer_mask"], apply_done=parts["applied"],
            queue_depth=parts["qdepth"],
            txn_watch=parts.get("txn_watch"),
            txn_term=parts.get("txn_term"))


@functools.lru_cache(maxsize=None)
def arg_layout(cfg: LogConfig, n_replicas: int, K: int = 1,
               txn: bool = False) -> ArgLayout:
    """The :class:`ArgLayout` of a K-step dispatch (a single step is
    K = 1) of ``n_replicas``-wide groups; ``txn`` adds the two watch
    words of the transaction lane's step."""
    B = cfg.batch_slots
    shapes = [("data", (K, B, cfg.slot_words)), ("meta", (K, B, META_W)),
              ("count", (K,)), ("peer_mask", (n_replicas,)),
              ("applied", ()), ("qdepth", ()), ("timeout", ())]
    if txn:
        shapes += [("txn_watch", ()), ("txn_term", ())]
    fields, off = [], 0
    for name, shape in shapes:
        fields.append((name, off, shape))
        off += int(np.prod(shape))
    tile = ARG_LANES * ARG_TILE_ROWS
    rows = -(-off // tile) * ARG_TILE_ROWS
    if K > 1:
        # a tile more than K - 1 steps' at the least (a toy geometry's
        # step can hide in the pad): :func:`arg_layout_of` reads K
        # back off the rows
        rows = max(rows, arg_layout(cfg, n_replicas, K - 1).rows
                   + ARG_TILE_ROWS)
    return ArgLayout(K=K, fields=tuple(fields), rows=rows)


def arg_layout_of(cfg: LogConfig, n_replicas: int, rows: int) -> ArgLayout:
    """The fused dispatch's :func:`arg_layout` of ``rows`` rows: how a
    burst or scan program, polymorphic in K as ever, reads K off the
    one argument it is handed."""
    K = 1
    while arg_layout(cfg, n_replicas, K).rows < rows:
        K += 1
    lay = arg_layout(cfg, n_replicas, K)
    assert lay.rows == rows, (rows, K, lay.rows)
    return lay


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class StepOutput:
    """Per-replica device→host results of one step (small scalars only; bulk
    committed payload is fetched separately, see ``fetch_rows``)."""

    term: jax.Array
    role: jax.Array
    leader_id: jax.Array
    voted_term: jax.Array     # durable vote pair — the host persists these
    voted_for: jax.Array      #   to HardState between steps
    head: jax.Array
    apply: jax.Array
    commit: jax.Array
    end: jax.Array
    hb_seen: jax.Array        # leader heartbeat arrived — reset election timer
    became_leader: jax.Array  # this replica won an election this step
    acked: jax.Array          # absorbed/verified the leader window this step
    accepted: jax.Array       # client entries actually appended from the
                              # batch (< batch_count ⟹ ring full: RETRY rest)
    peer_acked: jax.Array     # [R] — which peers acked THIS replica's
                              # window (meaningful on the leader; feeds the
                              # host failure detector, check_failure_count
                              # analog dare_server.c:1189-1227)
    leadership_verified: jax.Array  # read-index safety: a majority (dual
                              # majority in transit) accepted this leader's
                              # authority THIS step, so reads at commit are
                              # linearizable (rc_verify_leadership analog,
                              # dare_ibv_rc.c:1182-1280)
    burst_hint: jax.Array     # max queue depth heard from any self-claimed
                              # leader (identical on every host under full
                              # connectivity): hosts use it to agree on a
                              # fused multi-step burst size next iteration
    rebase_delta: jax.Array   # >0 when any heard end crossed
                              # cfg.rebase_threshold: the agreed uniform
                              # offset subtraction (min member head) for
                              # the coordinated i32 rollover. Identical
                              # on every host under full connectivity —
                              # NodeDaemon applies it collectively; the
                              # in-process drivers use their omniscient
                              # min-head instead (partition-safe).
    cfg_rescanned: jax.Array  # 1 when the full-ring config rescan RAN this
                              # step (some replica's cached config source
                              # was invalidated); the same on every replica
                              # of the group. 0 in every steady step: the
                              # proof that the conditional is not taken.
    # --- correctness-observability digest chain (audit=True only) ---
    # None in the default program: None leaves add no pytree nodes, so
    # the audit=False step is BYTE-IDENTICAL to the pre-audit program
    # (cache-key guarded by tests/test_audit.py).
    audit_start: Optional[jax.Array] = None    # i32 — first digested index
    audit_digest: Optional[jax.Array] = None   # [W] u32 — per-entry digests
    audit_term: Optional[jax.Array] = None     # [W] i32 — per-entry terms
    # --- device telemetry (telemetry=True only) ---
    # [T_N] u32 counter vector (see the T_* columns above): protocol
    # counts as the DEVICE saw them, reduced in-program to scalars so
    # readback is O(counters), never O(log). None in the default
    # program — telemetry=False steps stay byte-identical
    # (cache-key guarded by tests/test_device_obs.py).
    telemetry: Optional[jax.Array] = None
    # --- cross-group transaction lane (txn=True only) ---
    # i32 prepare vote (txn/lane.py constants) for the group's armed
    # watch, evaluated against THIS replica's post-absorb log. None in
    # the default program — txn=False steps stay byte-identical
    # (cache-key guarded by tests/test_txn.py).
    txn_vote: Optional[jax.Array] = None
    # --- packed readback row (:func:`scan_scalars`) ---
    # [len(SCAN_KEYS) + R] i32: every scalar above that the host rules
    # consume, the replica's config view and the ``peer_acked`` row, so
    # one dispatch costs ONE device->host read. Filled by the builders
    # of ``parallel/mesh.py`` (:func:`with_scalars`); None out of
    # ``replica_step`` itself.
    scal: Optional[jax.Array] = None


def make_step_input(cfg: LogConfig, n_replicas: int) -> StepInput:
    """An idle (no client traffic, no timeout) input."""
    i32 = jnp.int32
    return StepInput(
        batch_data=jnp.zeros((cfg.batch_slots, cfg.slot_words), i32),
        batch_meta=jnp.zeros((cfg.batch_slots, META_W), i32),
        batch_count=jnp.zeros((), i32),
        timeout_fired=jnp.zeros((), i32),
        peer_mask=jnp.ones((n_replicas,), i32),
        apply_done=jnp.zeros((), i32),
        queue_depth=jnp.zeros((), i32),
    )


def digest_fold(rows, *, xp=jnp):
    """The audit digest: one u32 mul-fold (FNV-1a accumulate + a
    murmur3-style finalizer so a low-order flip diffuses) per fused
    slot row, EXCLUDING the M_GIDX column (the coordinated i32
    rollover rewrites gidx in place — position binding comes from the
    ledger's absolute index instead; see the audit block in
    :func:`replica_step`).

    ONE implementation serves every digest producer — the ``audit=``
    compiled step variant, the jitted range re-digest
    (:func:`build_redigest`), and the host-side snapshot verification
    in ``consensus/snapshot.py`` (``xp=numpy``) — so device and host
    digests can never drift. The layout version is
    ``config.DIGEST_EPOCH``; bump it whenever this fold changes.

    ``rows``: ``[N, slot_words + META_W]`` u32, the LIVE columns of
    ring rows (``log.live_rows``: the ring's zero pad is no part of an
    entry and of no digest) (jnp or numpy — both wrap u32 arithmetic
    identically)."""
    u32 = xp.uint32
    prime = u32(0x01000193)                     # FNV-1a prime
    acc = xp.full((rows.shape[0],), 0x811C9DC5, u32)   # FNV offset basis
    gidx_col = live_slot_words(rows) + M_GIDX
    for c in range(rows.shape[1]):
        if c == gidx_col:
            continue
        acc = acc * prime + rows[:, c]
    acc = acc ^ (acc >> 15)
    acc = acc * u32(0x2C1B3C6D)
    acc = acc ^ (acc >> 12)
    acc = acc * u32(0x297A2D39)
    acc = acc ^ (acc >> 15)
    return acc


def build_redigest(cfg: LogConfig, *, window_slots: int):
    """Jitted ``[start, start + window_slots)`` digest pass over ONE
    replica's fused log row — the backfill instrument of the repair
    pipeline (``runtime/repair.py``): after a digest-verified snapshot
    re-install, the donor's committed range is re-digested on device
    and fed to the host-side audit ledger so the repaired range
    returns to fully-audited (gap-free) coverage, not just healed
    state.

    Exactly the ``audit=`` window fold (:func:`digest_fold` — shared),
    so backfilled digests are bit-comparable with live audit windows.
    Returns ``(digests u32[W], terms i32[W], gidx i32[W])``; the host
    validates the stamped gidx column against the expected indices
    (slot-recycling integrity — same rule as the replay path) and
    clips to the committed range.

    CACHE-KEY GUARD: engines cache the compiled fn in the shared
    ``STEP_CACHE`` under a distinct ``("redigest", W)``-marked key —
    default / repair-off programs and their keys are untouched
    (tests/test_repair.py pins it)."""
    W = int(window_slots)
    i32, u32 = jnp.int32, jnp.uint32
    sw = cfg.slot_words

    def fn(buf_row, start):
        g = start + jnp.arange(W, dtype=i32)
        rows = live_rows(buf_row[slot_of(g, cfg.n_slots)], sw)
        dig = digest_fold(rows.astype(u32))
        return dig, rows[:, sw + M_TERM].astype(i32), rows[:, sw + M_GIDX]
    return jax.jit(fn)


def _lex_argmax(valid: jax.Array, keys) -> jax.Array:
    """Index of the lexicographically-largest row among ``valid`` ones
    (ties → smallest index); -1 if none valid."""
    v = valid
    for k in keys:
        kk = jnp.where(v, k, I32_MIN)
        v = v & (kk == jnp.max(kk))
    return jnp.where(jnp.any(v), jnp.argmax(v).astype(jnp.int32), -1)


def _popcount_vec(bitmask: jax.Array, n: int) -> jax.Array:
    """[n] membership 0/1 vector from a bitmask."""
    r = jnp.arange(n, dtype=jnp.uint32)
    return jnp.bitwise_and(jnp.right_shift(bitmask, r), 1).astype(jnp.int32)


def replica_step(
    state: ReplicaState,
    inp: StepInput,
    *,
    cfg: LogConfig,
    n_replicas: int,
    axis_name: str = "replica",
    use_pallas: bool = False,
    interpret: bool = False,
    fanout: str = "gather",
    elections: bool = True,
    audit: bool = False,
    telemetry: bool = False,
    txn: bool = False,
    group_batch_axis: Optional[str] = None,
) -> Tuple[ReplicaState, StepOutput]:
    """One protocol step for this replica (call under ``shard_map`` over the
    ``replica`` mesh axis, or under ``vmap(axis_name=...)`` for single-chip
    simulation — see ``parallel/mesh.py``).

    ``fanout`` selects how the leader's window reaches followers:

    * ``"gather"`` — every replica ``all_gather``s a (zeroed-unless-leader)
      window and receivers SELECT the dominant claimant's row. Split-brain
      safe under arbitrary ``peer_mask`` partitions (two self-claimed
      leaders cannot corrupt each other's payload), at O(R·W·slot_bytes)
      ICI traffic per replica. Required for partition simulation.
    * ``"psum"`` — the leader's window is broadcast as a masked ``psum``:
      O(W·slot_bytes) per replica (bandwidth independent of R — the analog
      of the reference's per-follower delta writes costing the leader one
      NIC pass, ``dare_ibv_rc.c:1526-1642``). Sound ONLY under full
      connectivity (``peer_mask`` all-ones — the real ICI mesh, where a
      chip failure kills the whole program rather than partitioning it):
      with full pairwise hearing, Phase B leaves at most one replica in
      the LEADER role per step (any lower-term leader hears the higher
      term and steps down; same-term double-win is impossible by election
      safety), so the psum has at most one contributor and equals the
      dominant row the gather path would have selected. The tiny scalar
      claim gather is kept — receivers still term-gate absorption, so
      even a violated assumption degrades to a rejected window, not a
      corrupted log... except the summed payload itself; hence the
      partition-capable paths (SimCluster default, fuzzer) keep "gather".

    ``elections=False`` compiles the STABLE fast-path step: Phase B (one
    collective + the candidacy/vote logic) is statically removed. With no
    ``timeout_fired`` input set, the full step and the stable step compute
    bit-identical results — candidacies are the only thing Phase B can
    change — so a driver may freely dispatch the stable step on every
    iteration where no election timer fired (the latency hot path) and
    the full step otherwise. Term adoption from the control gather and
    window absorption still run, so a deposed leader steps down and a
    higher-term leader is followed even in stable steps.

    ``telemetry=True`` compiles the device-counter vector: one u32
    ``[T_N]`` row per replica per step (elections started, votes
    granted/denied, appends accepted, commit advance, unheard links,
    quorum width, log headroom — the T_* columns above), built from
    scalars already in registers and returned as the optional
    ``StepOutput.telemetry`` field. The host consumer is
    ``obs/device.py`` (never imported here); ``telemetry=False`` (the
    default) is byte-identical to the pre-telemetry program.

    ``audit=True`` compiles the silent-divergence digest chain: one
    u32 checksum per committed entry in the window ``[commit - W,
    commit)``, emitted as extra ``StepOutput`` fields (see the audit
    block below and the host-side ledger in ``obs/audit.py``; nothing
    from that host layer is ever called here). The followers of
    this design are passive in the replication hot path — one-sided
    window absorption lands bytes in log memory with no receiver-side
    end-to-end check — so bit corruption of replicated state is silent
    without it. ``audit=False`` (the default) is byte-identical to the
    pre-audit program.

    ``group_batch_axis`` is given by the group mappings alone
    (:func:`vmap_groups`): the name of the ``vmap`` axis that batches
    independent groups into one program. The config rescan's predicate
    is reduced over it too, so its ``lax.cond`` stays a conditional
    there (see the cost note at the rescan). ``None`` (every
    single-group mapping) traces the program without it, equation for
    equation.
    """
    assert fanout in ("gather", "psum"), fanout
    i32 = jnp.int32
    R, W = n_replicas, cfg.window_slots
    me = lax.axis_index(axis_name).astype(i32)
    heard = inp.peer_mask.astype(bool)                      # [R]

    in_new = _popcount_vec(state.bitmask_new, R)            # [R] 0/1
    in_old = _popcount_vec(state.bitmask_old, R)
    transit = (state.cid_state == int(ConfigState.TRANSIT)).astype(i32)
    # EXTENDED: the group was up-sized for a joiner that REPLICATES (it is
    # in bitmask_new, so the window fan-out and pruning floor include it)
    # but does not yet VOTE or count toward commit — quorum stays on the
    # old config until the joiner has caught up and the leader submits
    # TRANSIT (reference EXTENDED semantics: handle_server_join_request
    # up-sizes via an EXTENDED config, dare_ibv_ud.c:1024-1037, and the
    # joiner only joins quorums after EXTENDED→TRANSIT,
    # dare_server.c:1861-1937).
    ext = state.cid_state == int(ConfigState.EXTENDED)
    in_vote = jnp.where(ext, in_old, in_new)                # voting members
    maj_vote = jnp.sum(in_vote) // 2 + 1
    maj_old = jnp.sum(in_old) // 2 + 1
    # During joint consensus, old-config members must still vote (the win
    # condition demands a majority of BOTH configs — dare_server.c:1366-1373)
    i_member = (in_vote[me] > 0) | ((transit > 0) & (in_old[me] > 0))
    with jax.named_scope("control_gather"):
        my_lterm = last_term(state.log, state.end)

    # ------------------------------------------------------------------
    # Phase A — control gather (terms, roles, offsets, candidacies,
    # apply offsets for pruning).  The analog of reading peers' cached
    # SIDs / ctrl arrays (dare_ibv_rc.c:1182-1280).
    # ------------------------------------------------------------------
    with jax.named_scope("control_gather"):
        ctrl = jnp.zeros((C_N,), i32)
        ctrl = ctrl.at[C_TERM].set(state.term)
        ctrl = ctrl.at[C_ROLE].set(state.role)
        ctrl = ctrl.at[C_END].set(state.end)
        ctrl = ctrl.at[C_COMMIT].set(state.commit)
        ctrl = ctrl.at[C_LTERM].set(my_lterm)
        ctrl = ctrl.at[C_APPLY].set(
            jnp.minimum(inp.apply_done, state.commit))
        ctrl = ctrl.at[C_TMO].set(inp.timeout_fired)
        ctrl = ctrl.at[C_VTERM].set(state.voted_term)
        ctrl = ctrl.at[C_VFOR].set(state.voted_for)
        ctrl = ctrl.at[C_QDEP].set(inp.queue_depth)
        ctrl = ctrl.at[C_HEAD].set(state.head)
        allc = lax.all_gather(ctrl, axis_name)              # [R, C_N]

        g_term, g_end = allc[:, C_TERM], allc[:, C_END]
        g_lterm, g_apply = allc[:, C_LTERM], allc[:, C_APPLY]
        g_tmo = allc[:, C_TMO]

        # vote-record retention from the control gather (rc_replicate_vote
        # analog, dare_ibv_rc.c:1049): runs on EVERY step, so a replica that
        # was partitioned during an election still learns peers' durable vote
        # pairs once healed — identically in the full and stable paths.
        rec_upd0 = heard & (allc[:, C_VTERM] > state.vote_rec_term)
        vote_rec_term1 = jnp.where(rec_upd0, allc[:, C_VTERM],
                                   state.vote_rec_term)
        vote_rec_for1 = jnp.where(rec_upd0, allc[:, C_VFOR],
                                  state.vote_rec_for)

    # ------------------------------------------------------------------
    # Phase B — one-round election (start_election dare_server.c:1264,
    # voting :1526-1743, counting :1327-1518 — collapsed to one step).
    # Statically removed in the stable fast path (elections=False).
    # ------------------------------------------------------------------
    if not elections:
        with jax.named_scope("election"):
            new_voted_term = state.voted_term
            new_voted_for = state.voted_for
            vote_rec_term2 = vote_rec_term1
            vote_rec_for2 = vote_rec_for1
            win = jnp.zeros((), bool)
            became = jnp.zeros((), bool)
            max_heard = jnp.max(jnp.where(heard, g_term, I32_MIN))
            new_term = jnp.maximum(state.term, max_heard)
            role = jnp.where(new_term > state.term, int(Role.FOLLOWER),
                             state.role).astype(i32)
            i_lead = role == int(Role.LEADER)
            leader_id = jnp.where(new_term > state.term, -1,
                                  state.leader_id).astype(i32)
        with jax.named_scope("append"):
            log2, end2 = append_batch(
                state.log, state.end, state.head, inp.batch_data,
                inp.batch_meta,
                jnp.where(i_lead, inp.batch_count, 0).astype(i32), new_term)
        end1 = state.end
    else:
        with jax.named_scope("election"):
            is_cand = (g_tmo > 0) & (in_vote > 0)               # [R]
            cand_term = g_term + 1
            i_cand = is_cand[me] & (state.role != int(Role.LEADER))

            # voter logic (vote durability: the vote all_gather below
            # replicates the durable (voted_term, voted_for) pair to every
            # live peer, which RETAINS it in vote_rec_* — the
            # rc_replicate_vote analog; the host additionally persists the
            # pair to a HardState file between steps, and recovery restores
            # max(persisted, peer records) — see consensus/snapshot.py
            # recover_vote)
            can_grant = (
                heard & is_cand
                & (cand_term >= state.term)
                & ((cand_term > state.voted_term)
                   | ((cand_term == state.voted_term)
                      & (jnp.arange(R) == state.voted_for)))
                & ((g_lterm > my_lterm)
                   | ((g_lterm == my_lterm) & (g_end >= state.end)))
            )
            best = _lex_argmax(can_grant, [cand_term, g_lterm, g_end])
            my_vote = jnp.where(i_cand, me, jnp.where(i_member, best, -1))
            vote_cast = my_vote >= 0
            new_voted_term = jnp.where(
                vote_cast, jnp.maximum(state.voted_term, cand_term[my_vote]),
                state.voted_term)
            new_voted_for = jnp.where(vote_cast, my_vote, state.voted_for)

            vote_msg = jnp.stack([my_vote, new_voted_term, new_voted_for])
            g_votes = lax.all_gather(vote_msg, axis_name)       # [R, 3]
            votes = g_votes[:, 0]
            got = (votes == me) & heard
            # retain votes CAST THIS STEP immediately (the control-gather
            # retention above only carries pre-step pairs): the vote gather
            # doubles as same-step durable replication to every live peer
            rec_upd = heard & (g_votes[:, 1] > vote_rec_term1)
            vote_rec_term2 = jnp.where(rec_upd, g_votes[:, 1], vote_rec_term1)
            vote_rec_for2 = jnp.where(rec_upd, g_votes[:, 2], vote_rec_for1)
            win = (
                i_cand
                & (jnp.sum(got.astype(i32) * in_vote) >= maj_vote)
                & jnp.where(transit > 0,
                            jnp.sum(got.astype(i32) * in_old) >= maj_old, True)
            )

            # term adoption: everyone adopts the max term heard (incl.
            # candidacies); a deposed leader steps down here — the fencing of
            # server_to_follower (dare_server.c:2238).
            my_term1 = jnp.where(i_cand, state.term + 1, state.term)
            eff_term = jnp.where(is_cand, cand_term, g_term)
            max_heard = jnp.max(jnp.where(heard, eff_term, I32_MIN))
            new_term = jnp.maximum(my_term1, max_heard)

            role = jnp.where(
                win, int(Role.LEADER),
                jnp.where(new_term > my_term1, int(Role.FOLLOWER),
                          jnp.where(i_cand, int(Role.CANDIDATE), state.role)),
            ).astype(i32)
            became = win & (state.role != int(Role.LEADER))
            i_lead = role == int(Role.LEADER)
            leader_id = jnp.where(win, me,
                                  jnp.where(new_term > state.term, -1,
                                            state.leader_id)).astype(i32)

        # --------------------------------------------------------------
        # Phase C — leader append: NOOP on election (dare_server.c:1487),
        # then the client batch (get_tailq_message → log_append_entry,
        # dare_ibv_ud.c:780-790).
        # --------------------------------------------------------------
        with jax.named_scope("append"):
            noop_data = jnp.zeros((1, cfg.slot_words), i32)
            noop_meta = jnp.zeros((1, META_W), i32).at[0, M_TYPE].set(
                int(EntryType.NOOP))
            log1, end1 = append_batch(
                state.log, state.end, state.head, noop_data, noop_meta,
                jnp.where(became, 1, 0).astype(i32), new_term)
            log2, end2 = append_batch(
                log1, end1, state.head, inp.batch_data, inp.batch_meta,
                jnp.where(i_lead, inp.batch_count, 0).astype(i32), new_term)

    # ------------------------------------------------------------------
    # Phase D — leader fan-out. Window floored at the minimum reachable
    # member end (so laggards within W catch up — beyond W they need
    # snapshot recovery, the analog of force_log_pruning eviction,
    # dare_server.c:2069) and at the leader's own head (pruned entries
    # are gone).
    # ------------------------------------------------------------------
    with jax.named_scope("fanout"):
        others = heard & (in_new > 0) & (jnp.arange(R) != me)
        min_end = jnp.min(jnp.where(others, g_end, I32_MAX))
        wstart = jnp.clip(min_end, end2 - W, end2)
        wstart = jnp.maximum(jnp.maximum(wstart, state.head), 0)
        wcount = jnp.clip(end2 - wstart, 0, W)
        wdata, wmeta = extract_window(log2, wstart, W)
        prev_term = last_term(log2, wstart)     # of entry wstart - 1

        # pruning input: min apply over reachable members (leader-only use)
        min_apply = jnp.min(jnp.where(heard & (in_new > 0), g_apply, I32_MAX))

        msg_scal = jnp.zeros((S_N,), i32)
        msg_scal = msg_scal.at[S_VALID].set(1)
        msg_scal = msg_scal.at[S_WSTART].set(wstart)
        msg_scal = msg_scal.at[S_WCOUNT].set(wcount)
        msg_scal = msg_scal.at[S_TERM].set(new_term)
        msg_scal = msg_scal.at[S_PREV].set(prev_term)
        msg_scal = msg_scal.at[S_COMMIT].set(state.commit)
        msg_scal = msg_scal.at[S_HEAD].set(state.head)

        contrib = jnp.where(i_lead, 1, 0)
        gw_scal = lax.all_gather(msg_scal * contrib, axis_name)  # [R, S_N]

        # dominant leader: the highest-term valid claim this replica can hear
        claim = heard & (gw_scal[:, S_VALID] > 0)
        dom = _lex_argmax(claim, [gw_scal[:, S_TERM]])
        has_msg = dom >= 0
        dsafe = jnp.maximum(dom, 0)
        m_scal = gw_scal[dsafe]
        m_term = m_scal[S_TERM]

        if fanout == "psum":
            # single-contributor broadcast (see docstring for the safety
            # argument): O(W) bandwidth instead of O(R·W)
            m_data = lax.psum(wdata * contrib, axis_name)       # [W, sw]
            m_meta = lax.psum(wmeta * contrib, axis_name)       # [W, MW]
        else:
            gw_data = lax.all_gather(wdata * contrib, axis_name)  # [R, W, sw]
            gw_meta = lax.all_gather(wmeta * contrib, axis_name)  # [R, W, MW]
            m_data = gw_data[dsafe]
            m_meta = gw_meta[dsafe]

    # ------------------------------------------------------------------
    # Phase E — absorb (uniform; the leader absorbs its own window as a
    # no-op). Term gate = fencing; prev-term check = AppendEntries
    # consistency; backoff on mismatch = nextIndex rewind, expressed as
    # data (our advertised end drops, so the next window reaches lower).
    # ------------------------------------------------------------------
    with jax.named_scope("absorb"):
        use = has_msg & (m_scal[S_VALID] > 0) & (m_term >= new_term)
        new_term2 = jnp.where(use, jnp.maximum(new_term, m_term), new_term)
        role2 = jnp.where(
            use & ((m_term > new_term) | (dom != me)),
            jnp.where(i_lead & (dom == me), role, int(Role.FOLLOWER)),
            role).astype(i32)
        leader_id2 = jnp.where(use, dom, leader_id)
        i_lead2 = role2 == int(Role.LEADER)

        m_wstart, m_wcount = m_scal[S_WSTART], m_scal[S_WCOUNT]
        gap = m_wstart > end2
        local_prev = last_term(log2, m_wstart)
        prev_ok = (m_wstart == 0) | (local_prev == m_scal[S_PREV])
        can_absorb = use & ~gap & prev_ok

        log3, end3 = absorb_window(
            log2, end2, m_data, m_meta, m_wstart,
            jnp.where(can_absorb, m_wcount, 0))
        # backoff: advertised end rewinds to just before the mismatch (never
        # below commit — committed entries cannot conflict)
        end3 = jnp.where(use & ~gap & ~prev_ok,
                         jnp.maximum(m_wstart - 1, state.commit), end3)

        # follower commit/head riding the message (lazy, one step behind the
        # leader's scan — matching the reference's lazy commit push). The
        # advance is CLAMPED to W per step: the committed-config checkpoint
        # (Phase G) scans only the W-entry commit-crossing window, so an
        # unbounded jump (rejoiner with a long matching log but stale
        # commit) could carry a CONFIG entry past the scan unseen. W per
        # step is also the host's apply/replay catch-up rate, so the clamp
        # costs no end-to-end liveness.
        commit1 = jnp.where(
            can_absorb & ~i_lead2,
            jnp.maximum(state.commit,
                        jnp.minimum(jnp.minimum(m_scal[S_COMMIT], end3),
                                    state.commit + W)),
            state.commit)
        head1 = jnp.where(
            can_absorb,
            jnp.maximum(state.head, jnp.minimum(m_scal[S_HEAD], commit1)),
            state.head)

    # ------------------------------------------------------------------
    # CONFIG derivation — Raft's latest-configuration-in-the-log rule,
    # carried INCREMENTALLY: the live config (bitmask_old/new, cid_state,
    # epoch) is cached state backed by the log entry at ``cfg_src``. Each
    # step adopts any newer CONFIG arriving through the appended batch
    # (O(B)) or the absorbed window (O(W)) — data already in registers —
    # and only when the cached source entry is truncated or overwritten
    # does a full-ring rescan run, under ``lax.cond`` (rare: divergence
    # backoff / conflicting absorb). The rescan branch reproduces the
    # original rule exactly — newest CONFIG retained in [head, end), else
    # the committed checkpoint — so truncating an uncommitted CONFIG
    # still rolls the config back (no abandoned-config trap).
    #
    # Cost honesty (what the v5e trace showed; PERF.md section 6, PR 30):
    # the rescan is the only ring-sized work of the step, so its
    # conditional has to be real in every mapping. On a replica's OWN
    # flag it was not: under ``vmap`` a batched predicate lowers
    # ``lax.cond`` to ``select_n`` and BOTH branches ran every step (4.1
    # ms of a 9.6 ms dispatch at 3 x 131072 slots, 9.4 of 22.0 ms at 7;
    # 39 us on four chips, where the flag is a device's own scalar). So
    # the branch is gated on "ANY replica of the group invalid": each
    # replica's flag rides the ack gather of Phase F, issued before the
    # rescan (its inputs are all known by then; no new collective), and
    # a value reduced over ``axis_name`` is UNBATCHED under
    # ``vmap(axis_name=...)``, so the ``cond`` stays a ``cond``. Inside
    # the taken branch each replica keeps ``where(cfg_invalid, rescanned,
    # kept)``: bit for bit the per-replica rule. Unheard peers' flags
    # count too: the flag decides only whether the expensive branch
    # runs, never what a replica adopts. On a mesh a rare rescan runs on
    # every chip at once, which is harmless. The group engines batch
    # this step over groups with a second ``vmap`` (:func:`group_step`,
    # the ``build_spmd_group_*`` builders), under which a per-group
    # predicate is batched again and the select came back (3.5 ms of a
    # 12.9 ms dispatch at 3 groups x 3 replicas, PERF.md section 6, PR
    # 44). That ``vmap`` is NAMED (:func:`vmap_groups`) and the
    # predicate is reduced over it as well, by one ``pmax`` of one
    # scalar, to "ANY replica of ANY group batched here": unbatched in
    # every mapping. The same argument holds across groups: a group
    # whose caches are all valid keeps them, bit for bit, through a
    # step in which another group rescanned. ``StepOutput.
    # cfg_rescanned`` says whether the branch ran. CONFIG entries take
    # effect from append/absorb time (poll_config_entries,
    # dare_server.c:2133-2187). Runs BEFORE the commit scan (joint
    # consensus needs the new quorum rules from append time).
    # ------------------------------------------------------------------
    with jax.named_scope("cfg_rescan"):
        wend_abs = m_wstart + m_wcount
        # invalidation: source truncated away (divergence backoff or
        # in-window conflict both leave end3 at/below it) …
        stale_src = state.cfg_src >= end3
        # … or overwritten by an absorbed window row that is no longer the
        # same CONFIG entry
        wp = jnp.clip(state.cfg_src - m_wstart, 0, W - 1)
        # same gidx + type is NOT enough: a new leader's conflicting CONFIG
        # at the same index is a different entry — the term disambiguates
        same_entry = ((m_meta[wp, M_GIDX] == state.cfg_src)
                      & (m_meta[wp, M_TYPE] == int(EntryType.CONFIG))
                      & (m_meta[wp, M_TERM] == state.cfg_src_term))
        replaced = (can_absorb & (state.cfg_src >= m_wstart)
                    & (state.cfg_src < wend_abs) & ~same_entry)
        cfg_invalid = (state.cfg_src >= 0) & (stale_src | replaced)

    # the ack gather of Phase F, issued here: it carries ``cfg_invalid``
    # to the rescan's predicate below
    with jax.named_scope("ack_quorum"):
        my_ack = jnp.where(can_absorb, m_wstart + m_wcount, 0).astype(i32)
        ack_msg = jnp.stack([my_ack, jnp.where(can_absorb, dom, -1),
                             cfg_invalid.astype(i32)])
        g_acks = lax.all_gather(ack_msg, axis_name)             # [R, 3]

    with jax.named_scope("cfg_rescan"):
        any_invalid = jnp.any(g_acks[:, 2] > 0)
        if group_batch_axis is not None:
            any_invalid = lax.pmax(any_invalid.astype(i32),
                                   group_batch_axis) > 0

        def _cfg_keep(_):
            return (state.cfg_src, state.cfg_src_term, state.bitmask_old,
                    state.bitmask_new, state.cid_state, state.epoch)

        def _cfg_rescan(_):
            # the one place that must see every slot: a column view of the
            # whole ring (log.ring_meta) is what it costs; the row found
            # is then read as a row
            all_meta = ring_meta(log3)
            all_gidx = all_meta[:, M_GIDX]
            live = ((all_meta[:, M_TYPE] == int(EntryType.CONFIG))
                    & (all_gidx >= head1) & (all_gidx < end3))
            pos = _lex_argmax(live, [all_gidx])
            found = pos >= 0
            psafe = jnp.maximum(pos, 0)
            w, wm = rows_at(log3, psafe)    # psafe < n_slots: its own slot
            scanned = (
                jnp.where(found, all_gidx[psafe], -1),
                jnp.where(found, wm[M_TERM], 0),
                jnp.where(found, w[0].astype(jnp.uint32), state.ccfg_old),
                jnp.where(found, w[1].astype(jnp.uint32), state.ccfg_new),
                jnp.where(found, w[2], state.ccfg_cid),
                jnp.where(found, w[3], state.ccfg_epoch))
            return tuple(jnp.where(cfg_invalid, a, b)
                         for a, b in zip(scanned, _cfg_keep(None)))

        (base_src, base_sterm, base_old, base_new, base_cid,
         base_epoch) = lax.cond(any_invalid, _cfg_rescan, _cfg_keep, None)

        # newest CONFIG in the absorbed window (followers learn configs here)
        w_offs = jnp.arange(W, dtype=i32)
        w_gidx = m_wstart + w_offs
        w_is_cfg = (can_absorb & (w_offs < m_wcount)
                    & (m_meta[:, M_TYPE] == int(EntryType.CONFIG))
                    & (m_meta[:, M_GIDX] == w_gidx)
                    & (w_gidx >= head1) & (w_gidx < end3))
        wpos = _lex_argmax(w_is_cfg, [w_gidx])
        w_words = m_data[jnp.maximum(wpos, 0)]
        w_src = jnp.where(wpos >= 0, m_wstart + wpos, -1)

        # newest CONFIG in the just-appended batch (the leader learns its
        # own submissions here — its fan-out window may trail its end)
        Bn = inp.batch_meta.shape[0]
        b_offs = jnp.arange(Bn, dtype=i32)
        b_is_cfg = ((b_offs < (end2 - end1))
                    & (inp.batch_meta[:, M_TYPE] == int(EntryType.CONFIG))
                    & ((end1 + b_offs) < end3))
        bpos = _lex_argmax(b_is_cfg, [b_offs])
        b_words = inp.batch_data[jnp.maximum(bpos, 0)]
        b_src = jnp.where(bpos >= 0, end1 + bpos, -1)

        # adopt the candidate with the largest (gidx, term) — an absorbed
        # window row at the SAME gidx as the base but a newer term is a new
        # leader's conflicting CONFIG and must win; ties/absences fall back
        # to the base cache (index 0)
        w_term = m_meta[jnp.maximum(wpos, 0), M_TERM]
        cand_src = jnp.stack([base_src, w_src, b_src])
        cand_sterm = jnp.stack([
            base_sterm, jnp.where(wpos >= 0, w_term, 0),
            jnp.where(bpos >= 0, new_term, 0)])
        cand_old = jnp.stack([base_old, w_words[0].astype(jnp.uint32),
                              b_words[0].astype(jnp.uint32)])
        cand_new = jnp.stack([base_new, w_words[1].astype(jnp.uint32),
                              b_words[1].astype(jnp.uint32)])
        cand_cid = jnp.stack([base_cid, w_words[2], b_words[2]])
        cand_epoch = jnp.stack([base_epoch, w_words[3], b_words[3]])
        pick = _lex_argmax(cand_src >= -1, [cand_src, cand_sterm])
        pick = jnp.maximum(pick, 0)
        cfg_src2 = cand_src[pick]
        cfg_src_term2 = cand_sterm[pick]
        bm_old2 = cand_old[pick]
        bm_new2 = cand_new[pick]
        cid2 = cand_cid[pick]
        epoch2 = cand_epoch[pick]
        in_new2 = _popcount_vec(bm_new2, R)
        in_old2 = _popcount_vec(bm_old2, R)
        maj_old2 = jnp.sum(in_old2) // 2 + 1
        transit2 = (cid2 == int(ConfigState.TRANSIT)).astype(i32)
        # EXTENDED post-absorb: commit quorum on the old config (joiner
        # replicates but does not count) — same rule as the pre-step masks
        ext2 = cid2 == int(ConfigState.EXTENDED)
        q_mask2 = jnp.where(ext2, bm_old2, bm_new2)
        in_q2 = _popcount_vec(q_mask2, R)
        maj_q2 = jnp.sum(in_q2) // 2 + 1

    # ------------------------------------------------------------------
    # Phase F — ACK + quorum commit. The ack is the *verified match
    # offset* (everything ≤ the absorbed window end matches the leader's
    # log), gathered from all replicas — the analog of followers RDMA-
    # writing reply[] bytes into the leader's entries. The commit scan
    # itself is ops/quorum.commit_scan (Pallas on TPU), under the
    # POST-absorb membership config.
    # ------------------------------------------------------------------
    with jax.named_scope("ack_quorum"):
        acks_for_me = jnp.where(heard & (g_acks[:, 1] == me), g_acks[:, 0], 0)
        acks_pad = jnp.zeros((R_PAD,), i32).at[:R].set(acks_for_me)

        cwin_g = state.commit + jnp.arange(W, dtype=i32)
        cwin_data, cwin_meta = rows_at(log3, cwin_g)            # [W, ...]
        terms_win = cwin_meta[:, M_TERM]
        scanned = commit_scan(
            acks_pad, state.commit, new_term2, end3, terms_win,
            bm_old2, q_mask2, transit2, maj_old2, maj_q2,
            use_pallas=use_pallas, interpret=interpret)
        commit2 = jnp.where(
            i_lead2, jnp.maximum(state.commit, scanned), commit1)

    # ------------------------------------------------------------------
    # Phase G — apply echo, pruning, CONFIG application.
    # ------------------------------------------------------------------
    with jax.named_scope("apply_prune"):
        apply2 = jnp.clip(jnp.maximum(state.apply, inp.apply_done),
                          head1, commit2)
        # Pruning is lazy and pressure-gated, like the reference: the periodic
        # pruner only trims what every reachable member has applied
        # (log_pruning P1/P2/P3 invariants, dare_server.c:1996-2067), and
        # only once the ring is 3/4 full — so a transiently-partitioned
        # laggard can still catch up from the log; one pruned past must
        # snapshot-recover (host path), which is exactly the reference's
        # straggler-eviction semantics.
        pressure = (end3 - head1) > (3 * cfg.n_slots) // 4
        head2 = jnp.where(
            i_lead2 & pressure,
            jnp.clip(jnp.maximum(head1, min_apply), head1, apply2),
            head1)
        # FORCED pruning (force_log_pruning analog, dare_server.c:2069-2122):
        # a reachable member whose apply is frozen (wedged app) must not
        # block the ring forever. Under HARD pressure (7/8 full) the leader
        # advances the head past the laggard, bounded by its OWN applied
        # offset — every recycled entry is applied + persisted on the leader,
        # so the left-behind member can snapshot-recover from its store. The
        # laggard's host detects head > its apply cursor and stops replaying
        # (recycled slots must never reach the app) — see
        # SimCluster._replay_committed / need_recovery.
        hard = (end3 - head1) > (7 * cfg.n_slots) // 8
        head2 = jnp.where(i_lead2 & hard, jnp.maximum(head2, apply2), head2)

        # committed-config checkpoint: a CONFIG entry below commit can never
        # be truncated (backoff floors at commit), so it becomes the
        # fallback when the ring holds no live CONFIG entry (pruned past, or
        # every newer CONFIG was truncated). Incremental form: (a) promote
        # the live cache once its source entry commits; (b) scan the
        # commit-CROSSING window [state.commit, commit2) — bounded by W —
        # for an older CONFIG committing while a newer uncommitted one is
        # cached (two-configs-in-flight; the driver serializes changes so
        # this is a churn-replay corner). Newest-wins by epoch (epochs are
        # strictly increasing along the committed config order by
        # construction — MembershipManager bumps per change, and elastic
        # genesis re-types old-world CONFIGs to NOOP).
        crossed = ((cwin_meta[:, M_TYPE] == int(EntryType.CONFIG))
                   & (cwin_meta[:, M_GIDX] == cwin_g)
                   & (cwin_g < commit2))
        xpos = _lex_argmax(crossed, [cwin_g])
        xw = cwin_data[jnp.maximum(xpos, 0)]    # the row of entry commit+xpos
        x_found = xpos >= 0
        cc1_old = jnp.where(x_found & (xw[3] > state.ccfg_epoch),
                            xw[0].astype(jnp.uint32), state.ccfg_old)
        cc1_new = jnp.where(x_found & (xw[3] > state.ccfg_epoch),
                            xw[1].astype(jnp.uint32), state.ccfg_new)
        cc1_cid = jnp.where(x_found & (xw[3] > state.ccfg_epoch),
                            xw[2], state.ccfg_cid)
        cc1_epoch = jnp.where(x_found & (xw[3] > state.ccfg_epoch),
                              xw[3], state.ccfg_epoch)
        promote = (cfg_src2 >= 0) & (cfg_src2 < commit2) & (epoch2 > cc1_epoch)
        ccfg_old2 = jnp.where(promote, bm_old2, cc1_old)
        ccfg_new2 = jnp.where(promote, bm_new2, cc1_new)
        ccfg_cid2 = jnp.where(promote, cid2, cc1_cid)
        ccfg_epoch2 = jnp.where(promote, epoch2, cc1_epoch)

    # ------------------------------------------------------------------
    # Silent-divergence audit digests (audit=True only; statically
    # removed otherwise — the default program stays byte-identical).
    # One digest per entry in the window [commit2 - W, commit2): commit
    # advances at most W per step (the leader scans a W-entry window;
    # the follower advance is clamped to W), so consecutive windows
    # tile the committed prefix with NO gaps, and each entry is
    # RE-digested on every step while commit2 <= g + W — the host
    # ledger (obs/audit.py) both cross-checks replicas at matching
    # absolute indices and re-checks a replica's own earlier reports,
    # catching post-commit bit corruption of log memory. The mul-fold
    # covers the fused slot row (payload words + metadata incl. the
    # term column — the HardState binding) EXCEPT the M_GIDX column:
    # the coordinated i32 rollover rewrites gidx in place, and a
    # digest covering it would tear between replicas that digest the
    # same entry on opposite sides of a rollover; position binding
    # comes from the ledger's absolute index instead. Entries below
    # ``head`` are masked out (their slots may be recycled), which is
    # safe: g >= head implies the slot physically holds entry g (the
    # ring retains at most n_slots - 1 live entries).
    audit_start = audit_digest = audit_terms = None
    if audit:
        with jax.named_scope("audit_digest"):
            u32 = jnp.uint32
            a_g = (commit2 - W) + jnp.arange(W, dtype=i32)
            audit_start = jnp.maximum(jnp.maximum(commit2 - W, head2), 0)
            a_valid = a_g >= audit_start
            a_rows = live_rows(log3.buf[slot_of(a_g, cfg.n_slots)],
                               cfg.slot_words).astype(u32)
            # the fold lives in digest_fold — shared with the range
            # re-digest program and the host-side snapshot verification,
            # so no digest producer can drift from another
            audit_digest = jnp.where(a_valid, digest_fold(a_rows), u32(0))
            audit_terms = jnp.where(
                a_valid, a_rows[:, cfg.slot_words + M_TERM].astype(i32), 0)

    # ------------------------------------------------------------------
    # Device telemetry (telemetry=True only; statically removed
    # otherwise). Every value is a scalar already in registers — no
    # log reads, no collectives — so the vector costs a handful of
    # integer ops and its readback is O(T_N). Counter semantics are
    # DEVICE truth: what this replica's program actually did this
    # step, not what the host inferred (the gap this closes: unheard
    # links count the link-model drops/partitions as consumed by the
    # compiled step; quorum width is the ack count the commit scan
    # really saw; headroom is the ring occupancy inside the dispatch).
    # ------------------------------------------------------------------
    telemetry_vec = None
    if telemetry:
        with jax.named_scope("telemetry"):
            if elections:
                t_elec = i_cand.astype(i32)
                # granted = voted for ANOTHER replica's candidacy this
                # step; denied = heard candidacies (own excluded) that did
                # not get this replica's vote
                t_grant = (vote_cast & (my_vote != me)).astype(i32)
                n_cand = jnp.sum((is_cand & heard).astype(i32))
                t_deny = jnp.maximum(n_cand - t_elec - t_grant, 0)
            else:
                t_elec = t_grant = t_deny = jnp.zeros((), i32)
            telemetry_vec = jnp.stack([
                t_elec,
                t_grant,
                t_deny,
                (end2 - end1).astype(i32),
                (commit2 - state.commit).astype(i32),
                (R - jnp.sum(heard.astype(i32))).astype(i32),
                jnp.sum((heard & (g_acks[:, 1] == me)).astype(i32)),
                ((cfg.n_slots - 1) - (end3 - head2)).astype(i32),
            ]).astype(jnp.uint32)

    # ------------------------------------------------------------------
    # Cross-group transaction prepare-vote lane (txn=True only;
    # statically removed otherwise — the default program stays
    # byte-identical). The host coordinator arms a per-group watch
    # ``(prepare index, term)``; each replica reads the watched slot of
    # its OWN post-absorb log and votes (txn/lane.py): PREPARED when
    # the index committed under the watched term (or was already
    # pruned — pruning trails the host apply cursor, so a pruned index
    # was committed and replayed), CONFLICT when it committed under a
    # different term (a failover leader overwrote the prepare), else
    # PENDING. One gather-free slot read per replica — the vote rides
    # the SAME dispatch that replicated the prepare entries, which is
    # what makes a cross-group commit ~2 protocol steps.
    # ------------------------------------------------------------------
    txn_vote = None
    if txn:
        from rdma_paxos_tpu.txn.lane import prepare_vote
        t_w = (inp.txn_watch if inp.txn_watch is not None
               else jnp.full((), -1, i32))
        t_wt = (inp.txn_term if inp.txn_term is not None
                else jnp.zeros((), i32))
        t_row = log3.buf[slot_of(jnp.maximum(t_w, 0), cfg.n_slots)]
        txn_vote = prepare_vote(
            watch=t_w, watch_term=t_wt, head=head2, commit=commit2,
            entry_term=t_row[cfg.slot_words + M_TERM].astype(i32),
            entry_gidx=t_row[cfg.slot_words + M_GIDX].astype(i32))

    new_state = ReplicaState(
        log=log3, term=new_term2, role=role2, leader_id=leader_id2,
        voted_term=new_voted_term, voted_for=new_voted_for,
        vote_rec_term=vote_rec_term2, vote_rec_for=vote_rec_for2,
        head=head2, apply=apply2, commit=commit2, end=end3,
        cid_state=cid2, bitmask_old=bm_old2, bitmask_new=bm_new2,
        epoch=epoch2, cfg_src=cfg_src2, cfg_src_term=cfg_src_term2,
        ccfg_old=ccfg_old2, ccfg_new=ccfg_new2, ccfg_cid=ccfg_cid2,
        ccfg_epoch=ccfg_epoch2,
    )
    out = StepOutput(
        term=new_term2, role=role2, leader_id=leader_id2,
        voted_term=new_voted_term, voted_for=new_voted_for,
        head=head2, apply=apply2, commit=commit2, end=end3,
        hb_seen=(has_msg & use).astype(i32),
        became_leader=became.astype(i32),
        acked=can_absorb.astype(i32),
        accepted=(end2 - end1).astype(i32),
        peer_acked=(heard & (g_acks[:, 1] == me)).astype(i32),
        leadership_verified=(
            i_lead2
            & (jnp.sum((heard & (g_acks[:, 1] == me)).astype(i32)
                       * in_q2) >= maj_q2)
            & ((transit2 <= 0)
               | (jnp.sum((heard & (g_acks[:, 1] == me)).astype(i32)
                          * in_old2) >= maj_old2))).astype(i32),
        burst_hint=jnp.max(jnp.where(
            heard & (allc[:, C_ROLE] == int(Role.LEADER)),
            allc[:, C_QDEP], 0)).astype(i32),
        # coordinated i32-rollover signal: when any heard end crossed
        # the threshold, the agreed subtraction is the min PRE-step head
        # over ALL heard rows (every live offset stays >= 0), rounded
        # DOWN to a multiple of n_slots (slot = g % n_slots and entries
        # do not move, so the mapping must be preserved). The min is
        # deliberately NOT filtered by membership: bitmask_new skews by
        # one step during CONFIG adoption (leader adopts at append,
        # followers at absorb), and a membership-filtered min would let
        # hosts derive DIFFERENT deltas in that window — permanent
        # offset divergence. ``heard`` is the only mask that is
        # provably identical on every host under full connectivity; a
        # catching-up row's low head merely defers the rollover.
        rebase_delta=jnp.where(
            jnp.max(jnp.where(heard, g_end, 0))
            >= cfg.rebase_threshold,
            jnp.maximum(
                jnp.bitwise_and(
                    jnp.min(jnp.where(heard, allc[:, C_HEAD], I32_MAX)),
                    ~(cfg.n_slots - 1)),
                0),
            0).astype(i32),
        cfg_rescanned=any_invalid.astype(i32),
        audit_start=audit_start,
        audit_digest=audit_digest,
        audit_term=audit_terms,
        telemetry=telemetry_vec,
        txn_vote=txn_vote,
    )
    return new_state, out


# the LOCAL batch axis of the ``vmap`` that puts independent groups
# into one program. Not the mesh's ``GROUP_AXIS`` (``parallel/mesh.py``):
# on a 2-D mesh a reduction over this name stays on the chip.
GROUP_BATCH_AXIS = "group_batch"


def vmap_groups(fn):
    """``fn(state, inp)`` batched over a leading axis of independent
    groups, the axis named :data:`GROUP_BATCH_AXIS`: the one place the
    group mappings (:func:`group_step`, the ``build_spmd_group_*``
    builders of ``parallel/mesh.py``) take their outer ``vmap`` from.
    The :func:`replica_step` inside is given the same name as
    ``group_batch_axis``."""
    return jax.vmap(fn, in_axes=(0, 0), axis_name=GROUP_BATCH_AXIS)


def group_step(
    *,
    cfg: LogConfig,
    n_replicas: int,
    axis_name: str = "replica",
    use_pallas: bool = False,
    interpret: bool = False,
    fanout: str = "gather",
    elections: bool = True,
    audit: bool = False,
    telemetry: bool = False,
    txn: bool = False,
):
    """The group-batched protocol step: G independent consensus groups
    advanced by ONE program.

    :func:`replica_step` is documented as vmappable over the replica
    axis; sharding the keyspace across G groups adds a second leading
    ``group`` batch axis (:func:`vmap_groups`). Groups are fully
    independent state machines: no protocol state may ever cross the
    group axis, and XLA simply widens every tensor op and every
    replica-axis collective by a factor of G. G groups therefore
    replicate in ONE compiled dispatch instead of G (the
    sharded-throughput win ``benchmarks/shard_bench.py`` measures).

    The one exception, and why the axis has a name: the scalar that
    GATES the full-ring config rescan is reduced over the groups too
    ("some replica of some group here must rescan"), because only an
    unbatched predicate keeps ``lax.cond`` a conditional. It decides
    whether work runs, carries no protocol state, and inside the
    branch every replica still adopts on its own flag: a group whose
    config caches are valid leaves the step bit for bit as if it had
    run alone (``tests/test_membership_rescan.py``).

    Takes/returns pytrees with leading axes ``[group, replica, ...]``.

    CACHE-KEY GUARD: everything that shapes the compiled program is in
    this signature — the group count G deliberately is NOT. The
    returned callable is batch-size-polymorphic until ``jit``
    specializes it on the input shapes, so a homogeneous
    ``ShardedCluster`` (G groups sharing one ``LogConfig``) runs all
    its groups through exactly ONE compiled program per step variant,
    cached once in the shared runtime step cache
    (``runtime/sim.py:STEP_CACHE``; ``tests/test_shard.py`` proves the
    single-compile property).
    """
    import functools

    core = functools.partial(
        replica_step, cfg=cfg, n_replicas=n_replicas,
        axis_name=axis_name, use_pallas=use_pallas,
        interpret=interpret, fanout=fanout, elections=elections,
        audit=audit, telemetry=telemetry, txn=txn,
        group_batch_axis=GROUP_BATCH_AXIS)
    vstep = jax.vmap(core, in_axes=(0, 0), axis_name=axis_name)
    return vmap_groups(vstep)


# ---------------------------------------------------------------------------
# device-resident K-window scan: the consolidated minimal readback
# ---------------------------------------------------------------------------

# per-replica scalars the host rules actually consume, packed into ONE
# [..., len(SCAN_KEYS) + R] i32 row by :func:`scan_scalars`, so every
# dispatch (serial step, fused burst, K-window scan) hands the host a
# single array instead of one device->host transfer per field. The
# named columns come first; the ``peer_acked`` row (R columns) follows
# them. ``accepted`` carries the CUMULATIVE accepted count across a
# fused dispatch (the burst-sum semantics, computed in-program). The
# :data:`CONFIG_VIEW_KEYS` columns are the replica's config view,
# taken from the POST-step state — the view of the same step as the ``peer_acked``
# row the failure detector judges it with. Order is part of the host
# contract (:func:`unpack_scalars` reads by index) — append only.
CONFIG_VIEW_KEYS = ("bitmask_old", "bitmask_new", "cid_state", "epoch")
SCAN_KEYS = ("term", "role", "leader_id", "voted_term", "voted_for",
             "head", "apply", "commit", "end", "hb_seen",
             "became_leader", "acked", "accepted",
             "leadership_verified", "rebase_delta", "burst_hint",
             ) + CONFIG_VIEW_KEYS + ("cfg_rescanned",)


def scan_scalars(out: StepOutput, accepted_total: jax.Array,
                 state: ReplicaState) -> jax.Array:
    """One step's packed readback row: the :data:`SCAN_KEYS` columns
    then ``out.peer_acked``, along a trailing axis
    (``[..., len(SCAN_KEYS) + R]`` i32). ``accepted_total`` substitutes
    the cumulative accepted count for the per-step ``accepted`` field;
    ``state`` is the POST-step state the config view is read from (the
    u32 bitmasks travel bit-for-bit)."""
    cols = [accepted_total if k == "accepted"
            else getattr(state if k in CONFIG_VIEW_KEYS else out, k)
            for k in SCAN_KEYS]
    cols = [c if c.dtype == jnp.int32
            else lax.bitcast_convert_type(c, jnp.int32) for c in cols]
    return jnp.concatenate([jnp.stack(cols, axis=-1), out.peer_acked],
                           axis=-1)


def with_scalars(out: StepOutput, accepted_total: jax.Array,
                 state: ReplicaState) -> StepOutput:
    """``out`` carrying its packed readback row (``out.scal``) — what
    the step and burst builders return, so the host reads ONE array a
    dispatch while the field-by-field outputs stay for callers that
    want them (an output nobody reads costs no transfer)."""
    return dataclasses.replace(
        out, scal=scan_scalars(out, accepted_total, state))


def scan_readback(out: StepOutput, accepted_total: jax.Array,
                  state: ReplicaState, *, audit: bool,
                  telemetry: bool) -> dict:
    """One scan step's readback dict — the SINGLE assembly rule every
    scan builder uses (sim, group, spmd, spmd-group), so the
    consolidated-readback contract can never drift between engines:
    the :func:`scan_scalars` row, plus the per-step audit windows /
    telemetry vector only when those variants are compiled."""
    ys = dict(scal=scan_scalars(out, accepted_total, state))
    if audit:
        ys.update(audit_start=out.audit_start,
                  audit_digest=out.audit_digest,
                  audit_term=out.audit_term,
                  audit_commit=out.commit)
    if telemetry:
        ys["telemetry"] = out.telemetry
    return ys


def unpack_scalars(rows: np.ndarray) -> Dict[str, np.ndarray]:
    """Host inverse of :func:`scan_scalars`: the ``res`` dict of one
    step's rows (``[..., len(SCAN_KEYS) + R]``, already on the host) —
    THE unpack routine of step, burst and scan readbacks."""
    res = {k: rows[..., i] for i, k in enumerate(SCAN_KEYS)}
    for k in ("bitmask_old", "bitmask_new"):
        res[k] = res[k].astype(np.uint32)
    res["peer_acked"] = rows[..., len(SCAN_KEYS):]
    return res


def fetch_rows(log: Log, start: jax.Array, *, window_slots: int):
    """Host helper: the ``window_slots`` entries beginning at ``start``,
    fused (``[W, slot_words + META_W]``: payload words, then the
    framing metadata; the ring's pad stays on the device) — what the driver reads newly
    committed payloads for replay/persist through (the analog of
    apply_committed_entries walking the log,
    ``dare_server.c:1815-1974``). ONE array, because every array a
    program hands the host costs a read of its own (0.45-0.5 ms on the
    v5e's host whatever its size: PERF.md section 6, PR 48); the host
    takes the columns apart for nothing."""
    with jax.named_scope("replay_fetch"):
        return window_rows(log, start, window_slots)


def fetch_window(log: Log, start: jax.Array, *, window_slots: int):
    """:func:`fetch_rows` as ``(data, meta)``, the columns taken apart
    on the device (``extract_window``'s results, bit for bit)."""
    w = fetch_rows(log, start, window_slots=window_slots)
    return w[..., :log.slot_words], w[..., log.slot_words:]
