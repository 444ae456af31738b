"""Snapshot-based recovery — the joiner/straggler catch-up path.

Reference (§3.5 of SURVEY.md): a joiner RDMA-reads a donor's serialized
BerkeleyDB record stream plus the determinant of the last applied entry
(``snapshot_t``, ``dare_log.h:105-112``; ``rc_recover_sm``
``dare_ibv_rc.c:603-710``; ``proxy_apply_db_snapshot`` ``proxy.c:306-339``),
then RDMA-reads the log tail (``rc_recover_log`` ``:726-856``).

TPU-native equivalent: the app/event state travels as the stable store's
dump blob (host side, DCN); the device-side install sets the replica's log
offsets to the snapshot determinant ``(index, term)`` — the Raft
InstallSnapshot pair — and stamps the determinant term into the slot of
``index-1`` so the AppendEntries prev-term check passes and ordinary window
replication takes over from there (no special log-recovery path needed: the
leader's window floors at the restored ``end``).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from rdma_paxos_tpu.config import DIGEST_EPOCH
from rdma_paxos_tpu.consensus.log import (
    M_GIDX, M_TERM, live_rows, live_slot_words, slot_of)
from rdma_paxos_tpu.consensus.state import ReplicaState
from rdma_paxos_tpu.consensus.step import digest_fold
from rdma_paxos_tpu.obs import trace as obs_trace
from rdma_paxos_tpu.obs.metrics import default_registry
from rdma_paxos_tpu.obs.trace import default_ring


class SnapshotVerifyError(RuntimeError):
    """The snapshot's digest chain contradicts the audit ledger's
    majority digests: the DONOR is corrupted (or unverifiable). Raised
    by :func:`install_snapshot` BEFORE any state is touched — a
    corrupted donor is rejected at install time, never propagated; the
    repair pipeline retries with another majority donor."""


class SnapshotEpochError(SnapshotVerifyError):
    """The snapshot's digests were computed under a different digest
    LAYOUT (``config.DIGEST_EPOCH``): incomparable, not unequal —
    refuse rather than mis-verdict during a rolling digest upgrade."""


def _row_idx(group, r):
    """State-row index tuple: ``(r,)`` on the [R]-batched SimCluster
    state, ``(group, r)`` on the [G, R]-batched sharded state — the
    one place the snapshot path widens by the group axis."""
    return (r,) if group is None else (int(group), r)


@dataclasses.dataclass
class Snapshot:
    """Host-transferable snapshot: consensus determinant + event history.

    The config fields are the donor's COMMITTED-config checkpoint
    (``ccfg_*``), not its live adopted config: a live-but-uncommitted
    CONFIG entry always has ``gidx >= commit >= apply = index``, so the
    recovered replica re-absorbs it through ordinary window replication if
    it survives — and must NOT inherit it if it is truncated cluster-wide
    (the abandoned-config trap).

    ``digest_epoch``/``audit_start``/``audit_digests`` are the AUDIT
    CHAIN POSITION (``take_snapshot(digests=True)``): one u32 digest
    per physically-present committed entry ``[audit_start,
    audit_start + len)`` in ABSOLUTE indices (the donor's
    ``rebased_total`` folded in), computed with the same fold as the
    on-device audit windows (``consensus/step.py:digest_fold``).
    ``install_snapshot(ledger=...)`` verifies them against the
    ledger's majority digests and REFUSES a contradicting donor."""

    index: int            # last applied entry index + 1 (= donor apply)
    term: int             # term of entry index-1 (prev-check anchor)
    store_blob: bytes     # serialized stable store (full event history)
    epoch: int            # committed membership epoch at the donor
    bitmask_old: int
    bitmask_new: int
    cid_state: int
    # --- audit-chain binding (digests=True snapshots only) ---
    digest_epoch: int = 0              # digest LAYOUT version; 0 = none
    audit_start: int = -1              # ABSOLUTE index of audit_digests[0]
    audit_digests: Optional[np.ndarray] = None   # u32 [n]


def take_snapshot(state_b: ReplicaState, donor: int,
                  store_blob: bytes = b"",
                  index: Optional[int] = None, *,
                  group: Optional[int] = None,
                  digests: bool = False,
                  rebased_total: int = 0) -> Snapshot:
    """Capture a snapshot from replica ``donor`` of a batched state.

    Batched state carries the fused log as ``buf[R, n_slots,
    row_words]`` (``[G, R, ...]`` with ``group``; ``consensus/log.py``);
    the determinant term of entry ``apply-1`` lives at ``buf[..., slot,
    slot_words + M_TERM]``.

    ``index`` overrides the determinant index: pass the donor's HOST
    apply counter when the accompanying ``store_blob`` was produced by
    the host — the device-side ``apply`` can LAG the host's by one
    step's echo, and a snapshot whose index undershoots its store would
    make the recovered replica re-apply (and re-persist) records the
    store already holds.

    ``digests=True`` folds the donor's AUDIT CHAIN POSITION into the
    snapshot: its physically-present committed prefix ``[head, index)``
    is re-digested host-side with the device fold
    (``consensus/step.py:digest_fold``) and stamped in ABSOLUTE indices
    (``rebased_total`` added) together with ``config.DIGEST_EPOCH`` —
    the evidence ``install_snapshot(ledger=...)`` verifies against the
    ledger's majority digests so a corrupted donor is rejected, not
    propagated. Entries whose stamped gidx disagrees with the expected
    index (slot recycled mid-capture) truncate the chain from below."""
    log = state_b.log
    idx = _row_idx(group, donor)
    apply_ = (int(np.asarray(state_b.apply[idx])) if index is None
              else int(index))
    term = 0
    if apply_ > 0:
        slot = (apply_ - 1) & (log.n_slots - 1)
        # single-element device read — never pulls the full log to host
        term = int(log.buf[idx + (slot, log.slot_words + M_TERM)])
    digest_epoch, a_start, a_dig = 0, -1, None
    if digests:
        # one device->host pull of the donor's fused row, its live
        # columns; the digest chain is host-computed with the SHARED
        # fold (xp=numpy)
        sw = log.slot_words
        buf_np = np.asarray(live_rows(log.buf[idx], sw))
        n_slots = buf_np.shape[0]
        lo = max(int(np.asarray(state_b.head[idx])), 0)
        slots = (np.arange(lo, apply_) & (n_slots - 1)
                 if apply_ > lo else np.zeros(0, np.int64))
        rows = buf_np[slots]
        stamped = rows[:, sw + M_GIDX] if rows.size else rows[:, :0]
        good = stamped == np.arange(lo, apply_, dtype=stamped.dtype) \
            if rows.size else np.zeros(0, bool)
        # truncate from below past any recycled slot: the chain must
        # be contiguous up to the determinant
        first_good = int(len(good) - np.argmin(good[::-1])
                         if good.size and not good.all() else 0)
        rows = rows[first_good:]
        lo += first_good
        digest_epoch = DIGEST_EPOCH
        a_start = lo + int(rebased_total)
        a_dig = (digest_fold(rows.astype(np.uint32), xp=np)
                 if len(rows) else np.zeros(0, np.uint32))
    snap = Snapshot(
        index=apply_, term=term, store_blob=store_blob,
        epoch=int(np.asarray(state_b.ccfg_epoch[idx])),
        bitmask_old=int(np.asarray(state_b.ccfg_old[idx])),
        bitmask_new=int(np.asarray(state_b.ccfg_new[idx])),
        cid_state=int(np.asarray(state_b.ccfg_cid[idx])),
        digest_epoch=digest_epoch, audit_start=a_start,
        audit_digests=a_dig,
    )
    # host-side wrapper instrumentation (never inside the jitted body):
    # snapshot traffic is the recovery-path signal operators watch
    default_registry().inc("snapshots_taken_total")
    default_ring().record(obs_trace.SNAPSHOT_TAKEN, replica=donor,
                          index=snap.index, term=snap.term,
                          store_bytes=len(store_blob))
    return snap


def _install_body(state_b: ReplicaState, idx, index, term, cur_term,
                  voted_term, voted_for, epoch, bm_old, bm_new,
                  cid) -> ReplicaState:
    """Shared install body; ``idx`` is the state-row index tuple —
    ``(r,)`` for the [R]-batched state, ``(g, r)`` for the sharded
    [G, R]-batched state (the two thin jitted wrappers below)."""
    i32 = jnp.int32
    n_slots = state_b.log.n_slots
    slot_words = state_b.log.slot_words
    n_rec = state_b.vote_rec_term.shape[-1]
    # wipe the replica's fused log row and stamp the determinant term at the
    # slot of index-1 (the prev-term anchor for the first absorbed window)
    buf = state_b.log.buf.at[idx].set(0)
    anchor = slot_of(jnp.maximum(index - 1, 0), n_slots)
    buf = buf.at[idx + (anchor, slot_words + M_TERM)].set(
        jnp.where(index > 0, term, 0).astype(i32))
    log = dataclasses.replace(state_b.log, buf=buf)
    bm_old_u = bm_old.astype(jnp.uint32)
    bm_new_u = bm_new.astype(jnp.uint32)
    sets = dict(head=index, apply=index, commit=index, end=index,
                term=cur_term, role=1, leader_id=-1,
                voted_term=voted_term, voted_for=voted_for,
                # a fresh process has no memory of peers' votes
                vote_rec_term=jnp.zeros((n_rec,), i32),
                vote_rec_for=jnp.full((n_rec,), -1, i32),
                epoch=epoch, bitmask_old=bm_old_u, bitmask_new=bm_new_u,
                cid_state=cid,
                cfg_src=-1,      # cache backed by the checkpoint below
                cfg_src_term=0,

                # the snapshot's config IS the donor's committed-config
                # checkpoint (see Snapshot docstring); the wiped log holds
                # no CONFIG entries, so the first derivation falls back
                # here, and any surviving newer CONFIG re-arrives through
                # window replication
                ccfg_old=bm_old_u, ccfg_new=bm_new_u, ccfg_cid=cid,
                ccfg_epoch=epoch)
    out = {k: getattr(state_b, k).at[idx].set(
               jnp.asarray(v).astype(getattr(state_b, k).dtype))
           for k, v in sets.items()}
    return dataclasses.replace(state_b, log=log, **out)


@jax.jit
def _install(state_b: ReplicaState, r, *rest) -> ReplicaState:
    return _install_body(state_b, (r,), *rest)


@jax.jit
def _install_group(state_b: ReplicaState, g, r, *rest) -> ReplicaState:
    return _install_body(state_b, (g, r), *rest)


@jax.jit
def rebase_offsets(state_b: ReplicaState, delta) -> ReplicaState:
    """Subtract ``delta`` from every log offset of every replica — the
    coordinated i32-overflow rollover (LogConfig.rebase_threshold).

    Offsets are RELATIVE quantities everywhere in the protocol (window
    starts, acks, commit scans all compare offsets to each other), so a
    uniform subtraction is invisible to consensus as long as (a) every
    replica shifts in the same host iteration (the drivers guarantee
    it: SimCluster shifts the whole batched state between steps;
    NodeDaemon shifts collectively on a gathered, deterministic signal),
    (b) ``delta <= min(head)`` so no live offset goes negative, and
    (c) ``delta`` is a MULTIPLE OF n_slots — the slot of global index
    ``g`` is ``g % n_slots`` and entries do not move, so the mapping
    must be preserved (callers round the min head down).
    The stamped M_GIDX column shifts too; a recycled slot's stale gidx
    stays < head under uniform subtraction, so the liveness rule
    ``gidx >= head`` is preserved. The reference needs no analog — its
    u64 byte offsets outlive any deployment (dare_log.h:77-103).

    Works on the vmap-batched state and (transparently, no collectives)
    on a shard_map-sharded state: every operation is elementwise.
    ``delta`` is one number for all rows, or an array of the state's
    leading shape with each row's own (the sharded engine's ``[G, R]``:
    groups roll over independently)."""
    i32 = jnp.int32
    d = jnp.asarray(delta, i32)
    sw = state_b.log.slot_words
    gcol = sw + M_GIDX
    buf = state_b.log.buf
    buf = buf.at[..., gcol].add(-d[..., None])
    return dataclasses.replace(
        state_b,
        log=dataclasses.replace(state_b.log, buf=buf),
        head=state_b.head - d,
        apply=state_b.apply - d,
        commit=state_b.commit - d,
        end=state_b.end - d,
        cfg_src=jnp.where(state_b.cfg_src >= 0,
                          state_b.cfg_src - d, state_b.cfg_src),
    )


def export_row(state_b: ReplicaState, r: int) -> dict:
    """Pull replica ``r``'s full state row to host numpy — the transfer
    unit of cross-generation recovery (the analog of the joiner
    RDMA-reading the donor's snapshot buffer AND log tail in one shot,
    ``rc_recover_sm`` + ``rc_recover_log``, ``dare_ibv_rc.c:603-856``).
    Keys are ReplicaState field names; the log travels as ``log_buf``,
    the ring's LIVE columns (``[n_slots, slot_words + META_W]``: the pad
    is the device's, ``log.pad_rows`` puts it back at install)."""
    out = {"log_buf": np.asarray(
        live_rows(state_b.log.buf[r], state_b.log.slot_words))}
    for f in dataclasses.fields(ReplicaState):
        if f.name == "log":
            continue
        out[f.name] = np.asarray(getattr(state_b, f.name)[r])
    return out


def genesis_row(donor_row: dict, *, group_mask: int, epoch: int,
                n_replicas: int, term: Optional[int] = None) -> dict:
    """Sanitize a donor row into the shared GENESIS state of a new
    generation (elastic world rebuild — every member of the new world
    installs an identical copy, so the cluster boots pre-synchronized).

    Rules:

    * The log (and head/apply/commit/end) carries over verbatim — the
      donor is the most up-to-date survivor by Raft's election ordering
      ``(last_log_term, end)``, so its log contains every entry committed
      in the previous generation (Leader Completeness); its uncommitted
      suffix is carried as an ordinary suffix the next leader's NOOP
      commits or truncates.
    * Retained CONFIG entries are re-typed NOOP: slot numbering changes
      across generations, so an old-world bitmask must never resurface
      through the latest-config-in-the-log derivation. The new world's
      config is installed as both the live bitmasks and the committed
      checkpoint (``ccfg_*``).
    * ``term`` is bumped past every surviving member's term (caller
      passes the gathered max) so no vote or leadership claim from the
      dead world can conflict; votes and vote records reset — elections
      in the new world are fresh.
    * Roles reset to FOLLOWER; the new world elects normally.
    """
    from rdma_paxos_tpu.consensus.log import EntryType, M_TYPE
    from rdma_paxos_tpu.consensus.state import ConfigState, Role

    row = {k: np.array(v, copy=True) for k, v in donor_row.items()}
    buf = row["log_buf"]
    types = buf[:, live_slot_words(buf) + M_TYPE]
    types[types == int(EntryType.CONFIG)] = int(EntryType.NOOP)
    new_term = (int(row["term"]) if term is None else int(term)) + 1
    i32, u32 = np.int32, np.uint32
    mask = u32(group_mask)
    row.update(
        term=i32(new_term), role=i32(int(Role.FOLLOWER)),
        leader_id=i32(-1),
        voted_term=i32(0), voted_for=i32(-1),
        vote_rec_term=np.zeros(n_replicas, i32),
        vote_rec_for=np.full(n_replicas, -1, i32),
        cid_state=i32(int(ConfigState.STABLE)),
        bitmask_old=mask, bitmask_new=mask, epoch=i32(epoch),
        cfg_src=i32(-1),        # CONFIG entries were re-typed NOOP above
        cfg_src_term=i32(0),
        ccfg_old=mask, ccfg_new=mask,
        ccfg_cid=i32(int(ConfigState.STABLE)), ccfg_epoch=i32(epoch),
    )
    return row


def recover_vote(state_b: ReplicaState, r: int,
                 peers=None, *, group: Optional[int] = None) -> tuple:
    """Read replica ``r``'s replicated vote back from peers' vote records
    — the ``rc_get_replicated_vote`` analog (``dare_ibv_rc.c:394-473``).
    Returns the newest ``(voted_term, voted_for)`` any queried peer
    retains for ``r`` (query BEFORE installing a snapshot into ``r``).
    ``peers`` defaults to everyone EXCEPT ``r`` — a crashed replica's own
    in-memory record is exactly what the crash lost, so consulting it
    would mask real double-vote hazards in simulation. ``group``
    selects one consensus group's records on the sharded state."""
    rec_t = (state_b.vote_rec_term if group is None
             else state_b.vote_rec_term[group])
    rec_f = (state_b.vote_rec_for if group is None
             else state_b.vote_rec_for[group])
    if peers is None:
        peers = [p for p in range(rec_t.shape[0]) if p != r]
    sel = list(peers)
    vt = np.asarray(rec_t[sel, r])
    vf = np.asarray(rec_f[sel, r])
    if vt.size == 0:
        return 0, -1
    i = int(vt.argmax())
    return int(vt[i]), int(vf[i])


def verify_snapshot(snap: Snapshot, ledger, *, group: int = 0,
                    min_verified: int = 1) -> int:
    """Check ``snap``'s digest chain against ``ledger``'s
    MAJORITY-held digests (``obs/audit.py:AuditLedger``): every
    snapshot index the ledger retains with a replica-majority mask
    must carry the identical digest. Returns the number of verified
    indices; raises :class:`SnapshotVerifyError` on any contradiction
    (the donor is corrupted) or when fewer than ``min_verified``
    indices could be checked (an unverifiable donor is refused, not
    trusted), and :class:`SnapshotEpochError` on a digest-layout
    mismatch. Indices the ledger holds with only minority backing are
    SKIPPED — a first report may have come from the diverged minority
    itself, so only majority-held digests are evidence."""
    if snap.audit_digests is None or snap.audit_start < 0:
        raise SnapshotVerifyError(
            "snapshot carries no digest chain (take_snapshot("
            "digests=True) required for a verified install)")
    if snap.digest_epoch != ledger.digest_epoch:
        raise SnapshotEpochError(
            "snapshot digest epoch %d vs ledger epoch %d: layouts are "
            "incomparable — finish the rolling digest upgrade first"
            % (snap.digest_epoch, ledger.digest_epoch))
    maj = ledger.majority
    verified = 0
    chain = np.asarray(snap.audit_digests)
    # one bulk ledger read for the whole chain — per-index locking
    # would contend with the live readback thread for the entire walk
    entries = ledger.digest_range(group, snap.audit_start,
                                  snap.audit_start + len(chain))
    for i, (d, ent) in enumerate(zip(chain, entries)):
        if ent is None:
            continue
        _t, dd, mask = ent
        if bin(mask).count("1") < maj:
            continue
        if int(d) != dd:
            raise SnapshotVerifyError(
                "donor digest 0x%08x contradicts the ledger majority "
                "0x%08x at absolute index %d (group %d): corrupted "
                "donor rejected at install time"
                % (int(d), dd, snap.audit_start + i, group))
        verified += 1
    if verified < int(min_verified):
        raise SnapshotVerifyError(
            "only %d of the snapshot's %d chain indices are "
            "majority-covered by the ledger (need >= %d): donor is "
            "unverifiable" % (verified, len(snap.audit_digests),
                              min_verified))
    return verified


def install_snapshot(state_b: ReplicaState, r: int, snap: Snapshot, *,
                     voted_term: int = 0, voted_for: int = -1,
                     cur_term: int = 0, group: Optional[int] = None,
                     ledger=None, ledger_group: Optional[int] = None,
                     min_verified: int = 1) -> ReplicaState:
    """Install ``snap`` into replica ``r`` of a batched state: the replica
    resumes as a follower at the determinant; ordinary replication catches
    it up from there. The event-history blob is the host's concern
    (StableStore.load + app replay).

    ``voted_term``/``voted_for``/``cur_term`` restore election durability
    across the crash (HardState file + ``recover_vote`` peer records): the
    current term is floored at both the snapshot term and the recovered
    vote term, so a recovered replica can never re-grant a vote it already
    cast (reference ``rc_get_replicated_vote``).

    ``group`` installs into one consensus group of a sharded [G, R]
    state. ``ledger`` (an ``AuditLedger``) makes the install
    DIGEST-VERIFIED: :func:`verify_snapshot` runs FIRST and a
    contradicting (corrupted) donor raises before any state is
    touched — the repair pipeline's never-propagate guarantee."""
    if ledger is not None:
        lg = group if ledger_group is None else ledger_group
        verify_snapshot(snap, ledger, group=(lg or 0),
                        min_verified=min_verified)
    i32 = lambda v: jnp.asarray(v, jnp.int32)
    eff_term = max(int(snap.term), int(cur_term), int(voted_term))
    rest = (i32(snap.index), i32(snap.term),
            i32(eff_term), i32(voted_term), i32(voted_for),
            i32(snap.epoch), i32(snap.bitmask_old),
            i32(snap.bitmask_new), i32(snap.cid_state))
    if group is None:
        out = _install(state_b, i32(r), *rest)
    else:
        out = _install_group(state_b, i32(group), i32(r), *rest)
    # host-side wrapper instrumentation (the jitted _install stays
    # pure) — recorded AFTER the install so a raising _install (or a
    # refused verification) is never reported as an installed snapshot
    default_registry().inc("snapshots_installed_total")
    default_ring().record(obs_trace.SNAPSHOT_INSTALLED, replica=int(r),
                          index=snap.index, term=snap.term,
                          epoch=snap.epoch)
    return out
