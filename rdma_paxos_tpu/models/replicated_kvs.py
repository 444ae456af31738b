"""Standalone-DARE mode: the device KVS served directly over consensus.

In the reference, standalone DARE (no app interposition) replicates KVS
commands as CSM log entries and applies them through the ``dare_sm_t``
vtable (``dare_server.c:269``, ``dare_kvs_sm.c``); clients read via the
leader after a leadership verification (``ep_dp_reply_read_req``,
``dare_ep_db.c:132-161``).

Here: PUT/RM commands ride SEND entries through the same replicated log;
every replica folds its committed stream into its own device-resident
:mod:`rdma_paxos_tpu.models.kvs` table; linearizable GETs are served from
the leader's table only when the latest step verified leadership
(read-index). Weak (possibly stale) GETs can be served by any replica —
the same trade the reference's follower apps offer.
"""

from __future__ import annotations

import collections
import time
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from rdma_paxos_tpu.consensus.log import EntryType
from rdma_paxos_tpu.models.kvs import (
    CMD_W, OP_GET, OP_PUT, OP_RM, KVState, apply_cmd, decode_val,
    encode_cmd, make_kvs)
from rdma_paxos_tpu.txn.records import (
    TXN_ABORT, TXN_CMD_W, TXN_COMMIT, TXN_MERGE, TXN_PREPARE)
from rdma_paxos_tpu.runtime.sim import SimCluster

# capacity of the per-replica ring of finished (decided/complete)
# transaction ids: duplicate txn records (decisions and merges are
# retried under their ORIGINAL stamp across failover) trail their
# first committed copy by at most the retry patience plus a couple of
# confirmation dispatches, all of them SERIAL while the transaction
# is live (the coordinator's wants_serial gate), so the stream gap
# between a record and its last duplicate is a few hundred entries —
# orders of magnitude under this bound
TXN_DONE_CAP = 65536


class ReplicatedKVS:
    """KVS service over a :class:`SimCluster` (or a driver's cluster).

    ``cluster`` is duck-typed: any engine exposing the SimCluster
    client surface (``R``, ``submit``, ``replayed``, ``last``,
    ``obs``) works — the sharded layer reuses this class per group
    through exactly such a facade
    (:class:`rdma_paxos_tpu.shard.kvs._GroupFacade`), so sharding adds
    routing without forking the state-machine fold."""

    def __init__(self, cluster: SimCluster, cap: int = 4096):
        self.c = cluster
        # consensus group this instance serves (set by ShardedKVS);
        # labels the dedup metric series so per-group dedup pressure
        # is observable — None = unsharded, unlabeled legacy series
        self.group: Optional[int] = None
        self.tables: List[KVState] = [self._make_table(cap, r)
                                      for r in range(cluster.R)]
        self._cursor = [0] * cluster.R
        self._apply_jit = jax.jit(apply_cmd)
        self._get_many_jit = None      # compiled lazily on first batch
        self._get_cmds: dict = {}      # GET-encoding cache (hot keys)
        # per-replica endpoint registry: client_id -> highest applied
        # req_id (the dare_ep_db ``last_req_id`` analog,
        # dare_ep_db.h:20-30). Folded DETERMINISTICALLY from the
        # committed stream, so every replica — including any future
        # leader — skips retransmitted requests identically; dedup
        # therefore survives reconnects and failover
        # (dare_ibv_ud.c:1004-1014 dedups the same way at the leader).
        self.last_req: List[dict] = [dict() for _ in range(cluster.R)]
        self.deduped: List[int] = [0] * cluster.R
        # optional chaos.history.HistoryRecorder: when attached, every
        # client-visible operation (session PUT/RM, weak and read-index
        # GETs, retransmits) is recorded as invoke/ok/fail events for
        # the linearizability checker. Host-side bookkeeping only.
        self.history = None
        # txn staging + exactly-once (txn/records.py): per-replica
        # tid -> {"reqs": stamped reqs folded so far, "staged":
        # buffered kvs-command words}, folded DETERMINISTICALLY from
        # the committed stream like last_req — a PREPARE record stages
        # its embedded write, the COMMIT record applies the buffer in
        # staging order, ABORT drops it, MERGE applies immediately.
        # Writes of an aborted (or never-decided) transaction never
        # reach the table. Dedup is PER TID (every coordinator record
        # is uniquely stamped), so the registry holds only live tids:
        # a finished tid moves to the bounded done-ring below and its
        # entry here is dropped — where a per-conn high-water registry
        # would keep one entry per coordinator record forever.
        self._txn_buf: List[dict] = [dict() for _ in range(cluster.R)]
        self._txn_done: List[set] = [set() for _ in range(cluster.R)]
        self._txn_done_fifo: List[collections.deque] = [
            collections.deque() for _ in range(cluster.R)]
        self.txn_applied: List[int] = [0] * cluster.R
        self.txn_discarded: List[int] = [0] * cluster.R

    def _make_table(self, cap: int, r: int) -> KVState:
        """An empty table for replica ``r``, on the chip that holds
        replica ``r``'s log rows when the engine runs one replica per
        chip (``SimCluster.replica_device``) — a state machine on chip
        0 next to replica 0's log would make every other replica's
        apply and read cross chips. Engines without a per-replica
        placement (vmap rows, group facades) use the default device."""
        place = getattr(self.c, "replica_device", None)
        dev = place(r) if place is not None else None
        if dev is None:
            return make_kvs(cap)
        # built on ``dev`` and COMMITTED there: jit follows committed
        # operands, so applies and reads run where the table lives
        with jax.default_device(dev):
            return jax.device_put(make_kvs(cap), dev)

    def _spans(self):
        """The cluster's span recorder when causal tracing is on —
        session mutations are span births keyed (client_id, req_id),
        the same stamp that rides the entry's M_CONN/M_REQID columns
        (so the sim's append hook correlates them with (term, index))."""
        from rdma_paxos_tpu.obs.spans import active_recorder
        return active_recorder(getattr(self.c, "obs", None))

    def _span_rep(self, r: int) -> int:
        """Span-track replica id for local replica ``r``: the cluster
        may namespace replica ids (the sharded engine uses ``g*R + r``
        so per-group tracks never collide) — every span event this
        layer records must use the SAME namespace the cluster's
        append/commit/apply stamps use."""
        f = getattr(self.c, "span_replica", None)
        return f(r) if f is not None else r

    # ------------------------------------------------------------------

    def rebuild(self, r: int) -> None:
        """Crash-restart of replica ``r``'s app process: discard the
        device table and dedup registry (volatile) and refold from the
        replayed stream (the StableStore analog — replay IS the
        driver's recovery path). The fold is deterministic, so the
        rebuilt table, registry, and dedup decisions match exactly what
        the pre-crash incarnation derived."""
        self.tables[r] = self._make_table(int(self.tables[r].cap), r)
        self._cursor[r] = 0
        self.last_req[r] = dict()
        self.deduped[r] = 0
        self._txn_buf[r] = dict()
        self._txn_done[r] = set()
        self._txn_done_fifo[r] = collections.deque()
        self.txn_applied[r] = 0
        self.txn_discarded[r] = 0

    # ------------------------------------------------------------------

    def _fold(self, r: int) -> None:
        """Fold newly committed commands into replica r's table."""
        stream = self.c.replayed[r]
        n = len(stream)
        if self._cursor[r] >= n:
            return
        if hasattr(stream, "segments_from"):
            # consume ReplayBatch segments WITHOUT materializing the
            # stream: indexing would flatten the batches to legacy
            # tuples and destroy the log coordinates the streams/
            # tail followers decode for resume tokens and CDC records
            rows = []
            for seg in stream.segments_from(self._cursor[r]):
                rows.extend(seg.tuples() if hasattr(seg, "tuples")
                            else seg)
        else:
            rows = [stream[i] for i in range(self._cursor[r], n)]
        self._cursor[r] = n
        for etype, conn, req, payload in rows:
            if etype != int(EntryType.SEND):
                continue
            if len(payload) == TXN_CMD_W * 4:
                # 2PC record — the distinct width keeps legacy folds
                # skipping it; the (conn, req) dedup rule below covers
                # it in _fold_txn, so a coordinator retransmit after
                # failover stages/decides exactly once
                self._fold_txn(r, conn, req, payload)
                continue
            if len(payload) != CMD_W * 4:
                continue                      # not a KVS command: skip
            if req > 0 and conn > 0:
                # session-stamped command: apply exactly once
                if req <= self.last_req[r].get(conn, 0):
                    self.deduped[r] += 1
                    obs = getattr(self.c, "obs", None)
                    if obs is not None:
                        if self.group is not None:
                            obs.metrics.inc("kvs_deduped_total",
                                            replica=r, group=self.group)
                        else:
                            obs.metrics.inc("kvs_deduped_total",
                                            replica=r)
                    continue
                self.last_req[r][conn] = req
            cmd = jnp.asarray(np.frombuffer(payload, "<i4"))
            self.tables[r], _ = self._apply_jit(self.tables[r], cmd)

    def _txn_retire(self, r: int, tid: int) -> None:
        """Move ``tid`` to replica ``r``'s done-ring: late duplicates
        (retried decisions/merges) and stragglers of a finished
        transaction are dropped without per-record registry residue."""
        done = self._txn_done[r]
        if tid in done:
            return
        done.add(tid)
        fifo = self._txn_done_fifo[r]
        fifo.append(tid)
        while len(fifo) > TXN_DONE_CAP:
            done.discard(fifo.popleft())

    def _fold_txn(self, r: int, conn: int, req: int,
                  payload: bytes) -> None:
        """Fold one committed txn record (txn/records.py layout):
        PREPARE stages its embedded write per tid, COMMIT applies the
        tid's staged writes in staging order, ABORT drops them, MERGE
        applies immediately (commutative — no staging needed) and
        retires the tid once its last merge record lands. Exactly-once
        is per tid: stamped duplicates dedup against the live tid's
        req set or the done-ring, NOT the session ``last_req``
        registry (single-record coordinator conns would grow it
        forever). A record for an already-finished tid — a retried
        duplicate, or a PREPARE landing after its transaction's
        decision — is dropped, so nothing can stage under a dead tid.
        Deterministic over the committed stream, so every replica —
        and any rebuild — derives the same table."""
        from rdma_paxos_tpu.txn.records import decode_record
        txn_op, tid, arg, cmd_words = decode_record(payload)
        if tid in self._txn_done[r]:
            self.deduped[r] += 1
            return
        stamped = req > 0 and conn > 0
        buf = self._txn_buf[r]
        if txn_op in (TXN_PREPARE, TXN_MERGE):
            ent = buf.setdefault(tid, {"reqs": set(), "staged": []})
            if stamped:
                if req in ent["reqs"]:
                    self.deduped[r] += 1
                    return
                ent["reqs"].add(req)
            if txn_op == TXN_PREPARE:
                ent["staged"].append(np.asarray(cmd_words))
                return
            self.tables[r], _ = self._apply_jit(
                self.tables[r], jnp.asarray(cmd_words))
            self.txn_applied[r] += 1
            if stamped and len(ent["reqs"]) == arg:
                # the coordinator submits exactly ``arg`` merge
                # records here — all folded, the tid is complete
                del buf[tid]
                self._txn_retire(r, tid)
        elif txn_op == TXN_COMMIT:
            ent = buf.pop(tid, None)
            for cmd in (ent["staged"] if ent else ()):
                self.tables[r], _ = self._apply_jit(
                    self.tables[r], jnp.asarray(cmd))
                self.txn_applied[r] += 1
            self._txn_retire(r, tid)
        elif txn_op == TXN_ABORT:
            ent = buf.pop(tid, None)
            self.txn_discarded[r] += (len(ent["staged"]) if ent
                                      else 0)
            self._txn_retire(r, tid)

    # ------------------------------------------------------------------

    def put(self, leader: int, key: bytes, val: bytes, *,
            client_id: int = 0, req_id: int = 0) -> None:
        self.c.submit(leader, encode_cmd(OP_PUT, key, val).tobytes(),
                      conn=client_id, req_id=req_id)

    def remove(self, leader: int, key: bytes, *,
               client_id: int = 0, req_id: int = 0) -> None:
        self.c.submit(leader, encode_cmd(OP_RM, key).tobytes(),
                      conn=client_id, req_id=req_id)

    def merge(self, leader: int, op: int, key: bytes, val: bytes, *,
              client_id: int = 0, req_id: int = 0) -> None:
        """Submit one mergeable write (OP_INCR/OP_SADD/OP_MAX) — a
        plain single-group command; the txn fast path rides these."""
        self.c.submit(leader, encode_cmd(op, key, val).tobytes(),
                      conn=client_id, req_id=req_id)

    def session(self, client_id: int) -> "ClientSession":
        """Open a retransmitting-client session (the UD-client analog)."""
        return ClientSession(self, client_id)

    def serving_path(self, r: int) -> str:
        """The linearizable serving gate as a standalone check:
        ``"lease"`` / ``"read_index"`` when replica ``r`` may serve a
        linearizable read NOW (see :meth:`get` for the two paths),
        ``"quarantined"`` / ``"refused"`` when it must not. Callers
        that establish the linearization point themselves (the
        ReadHub, the txn coordinator's serialization-point reads) pair
        this with :meth:`serve_local` — unlike :meth:`get`'s ``None``,
        the gate verdict is never ambiguous with a missing key."""
        # a quarantined/recovering replica must not serve at all —
        # not even through a stale leadership_verified snapshot
        # from the step before its links were cut (the repair
        # pipeline revokes its lease; this closes the one-step
        # read-index window too). read_blocked covers the repair
        # holds need_recovery does not: the storm policy leaves
        # replay running, and the digest path drops need_recovery
        # at install time while probation still bars serving.
        if (r in getattr(self.c, "need_recovery", ())
                or r in getattr(self.c, "read_blocked", ())):
            return "quarantined"
        lm = getattr(self.c, "leases", None)
        g = self.group if self.group is not None else 0
        last = self.c.last
        # the serving frontier gate the hub also enforces: the
        # local apply cursor must cover the replica's own commit
        # index, else state already ACKED to writers is missing
        # from the table (a wedged apply keeps acking windows, so
        # leadership_verified — and the lease — stay live while
        # applied freezes below commit)
        applied = getattr(self.c, "applied", None)
        caught_up = (last is not None and applied is not None
                     and int(applied[r])
                     >= int(last["commit"][r]))
        if caught_up and lm is not None and lm.valid(g, r):
            return "lease"
        if caught_up and last["leadership_verified"][r]:
            return "read_index"
        return "refused"

    def get(self, r: int, key: bytes, *,
            linearizable: bool = False) -> Optional[bytes]:
        """Read from replica ``r``'s table. A ``linearizable=True``
        read serves through one of two zero-log-traffic paths:

        * **lease** — ``r`` holds a valid step-domain leader lease
          (``cluster.leases`` attached via ``runtime/reads.py``): no
          per-read verification round at all, the renewal rides the
          heartbeat/quorum machinery the protocol already runs;
        * **read_index** — ``r`` verified leadership on the latest
          finished step (the pre-lease rule, and the fallback a new
          leader uses while it waits out the old lease).

        Refused (returns None, recorded as a FAIL — the read
        definitively did not happen) when neither holds."""
        t0 = time.monotonic() if linearizable else None
        op_id = (self.history.invoke("get", key, replica=r,
                                     weak=not linearizable)
                 if self.history is not None else None)
        path = None
        if linearizable:
            path = self.serving_path(r)
            if path == "quarantined":
                if op_id is not None:
                    self.history.fail(op_id, reason="quarantined")
                return None
            if path == "refused":
                # a REFUSED read definitively did not happen — fail,
                # not timeout (the checker drops it, constraint-free)
                if op_id is not None:
                    self.history.fail(op_id,
                                      reason="leadership_unverified")
                return None
        self._fold(r)
        _, out = self._apply_jit(self.tables[r],
                                 jnp.asarray(encode_cmd(OP_GET, key)))
        v = decode_val(np.asarray(out))
        v = v if v else None
        if path is not None:
            from rdma_paxos_tpu.runtime.reads import count_read
            count_read(getattr(self.c, "obs", None), path, r,
                       group=self.group, t0=t0)
        if op_id is not None:
            self.history.ok(op_id, v)
        return v

    def serve_local(self, r: int, key: bytes) -> Optional[bytes]:
        """Bare local table read (fold + lookup) with NO linearization
        gate and NO accounting — the serve callback for hub-queued
        reads, whose linearization point (lease validity or confirmed
        read index + apply frontier) the :class:`ReadHub` establishes
        before invoking it."""
        self._fold(r)
        _, out = self._apply_jit(self.tables[r],
                                 jnp.asarray(encode_cmd(OP_GET, key)))
        v = decode_val(np.asarray(out))
        return v if v else None

    # batched local GETs: one vmapped dispatch per power-of-two tier
    # instead of a per-key apply dispatch — how a leaseholder (or a
    # read-index follower) serves a read BURST cheaply
    _GET_TIERS = (1, 2, 4, 8, 16, 32, 64, 128, 256, 512)

    def get_many(self, r: int, keys) -> List[Optional[bytes]]:
        """Serve a batch of local reads from replica ``r``'s table in
        ONE vmapped device dispatch (padded to a power-of-two tier so
        compiles stay bounded). Linearization gating and accounting
        are the CALLER's job — this is the serving primitive the
        lease/read-index paths and the read-mix bench share."""
        if not keys:
            return []
        self._fold(r)
        if self._get_many_jit is None:
            self._get_many_jit = jax.jit(jax.vmap(
                lambda kv, cmd: apply_cmd(kv, cmd)[1],
                in_axes=(None, 0)))
        out: List[Optional[bytes]] = []
        i = 0
        while i < len(keys):
            chunk = keys[i:i + self._GET_TIERS[-1]]
            tier = next(t for t in self._GET_TIERS
                        if t >= len(chunk))
            cmds = np.zeros((tier, CMD_W), "<i4")
            for j, k in enumerate(chunk):
                # hot read sets repeat keys: cache their encodings
                row = self._get_cmds.get(k)
                if row is None:
                    row = encode_cmd(OP_GET, k)
                    if len(self._get_cmds) < 65536:
                        self._get_cmds[k] = row
                cmds[j] = row
            vals = np.asarray(self._get_many_jit(
                self.tables[r], jnp.asarray(cmds)))
            for j in range(len(chunk)):
                v = decode_val(vals[j])
                out.append(v if v else None)
            i += len(chunk)
        return out

    def items_in_range(self, r: int, lo: bytes,
                       hi: Optional[bytes]) -> List[Tuple[bytes, bytes]]:
        """Every live ``(key, value)`` pair in ``[lo, hi)`` (byte-
        lexicographic; ``hi=None`` = unbounded) from replica ``r``'s
        folded table, sorted by key — the topology transition's
        donor-side enumeration primitive (what must be seeded into a
        migrating range's new owner, and the input to its range
        digest). Host-side table walk, no device dispatch. Keys come
        back canonicalized modulo trailing NULs (the fixed-width table
        cannot represent them — same equivalence the KVS itself
        applies)."""
        self._fold(r)
        kv = self.tables[r]
        used = np.asarray(kv.used)
        keys = np.asarray(kv.keys)
        vals = np.asarray(kv.vals)
        out: List[Tuple[bytes, bytes]] = []
        for slot in np.nonzero(used)[0]:
            kb = keys[slot].astype("<i4").tobytes().rstrip(b"\x00")
            if kb < lo or (hi is not None and kb >= hi):
                continue
            out.append(
                (kb, vals[slot].astype("<i4").tobytes().rstrip(b"\x00")))
        out.sort()
        return out

    def submit_get(self, leader: int, key: bytes, *, client_id: int,
                   req_id: int) -> None:
        """The READS-THROUGH-LOG baseline: ride a stamped ``OP_GET``
        entry through the replicated log like a write — appended,
        quorum-acked, committed, folded (the dedup registry marks its
        ``req_id``, so completion is observable via ``last_req``).
        This is what every linearizable read cost before leases; the
        read-mix bench A/Bs the lease path against it."""
        self.c.submit(leader, encode_cmd(OP_GET, key).tobytes(),
                      conn=client_id, req_id=req_id)


class ClientSession:
    """A client endpoint that may RETRANSMIT requests (after a timeout, a
    reconnect, or a leader failover) — the reference's UD client whose
    duplicates the leader drops via ``last_req_id``
    (``dare_ep_db.h:20-30``, ``dare_ibv_ud.c:1004-1014``).

    Every mutation is stamped ``(client_id, req_id)`` end-to-end: the pair
    rides the entry's ``M_CONN``/``M_REQID`` columns through the log, and
    every replica's fold skips any request at-or-below the client's
    applied high-water mark — so a duplicate appended by ANY leader (the
    one that crashed after committing, or the new one the client retried
    against) applies exactly once, in first-commit order.

    PROTOCOL CONTRACT (same as the reference's single ``last_req_id``
    slot per endpoint, ``dare_ep_db.h:20-30``, and Raft client
    sessions): a session keeps AT MOST ONE request outstanding — issue
    ``put``, and if no ack arrives, ``retransmit_put`` the SAME req_id
    until it commits, before issuing the next req_id. A client that
    pipelines req N+1 before req N's fate is known can lose req N: if N
    was truncated uncommitted and N+1 commits first, the high-water mark
    passes N and every later retransmit of N is dropped as a duplicate."""

    def __init__(self, kvs: ReplicatedKVS, client_id: int):
        if client_id <= 0:
            raise ValueError("client_id must be positive")
        self.kvs = kvs
        self.client_id = client_id
        self.req_id = 0

    def put(self, leader: int, key: bytes, val: bytes) -> int:
        """Submit a PUT; returns its req_id (keep it to retransmit)."""
        self.req_id += 1
        if self.kvs.history is not None:
            self.kvs.history.invoke("put", key, val,
                                    client=self.client_id,
                                    req_id=self.req_id, replica=leader)
        spans = self.kvs._spans()
        if spans is not None:
            spans.begin(self.client_id, self.req_id,
                        self.kvs._span_rep(leader), phase="submit")
        self.kvs.put(leader, key, val, client_id=self.client_id,
                     req_id=self.req_id)
        return self.req_id

    def remove(self, leader: int, key: bytes) -> int:
        self.req_id += 1
        if self.kvs.history is not None:
            self.kvs.history.invoke("rm", key, client=self.client_id,
                                    req_id=self.req_id, replica=leader)
        spans = self.kvs._spans()
        if spans is not None:
            spans.begin(self.client_id, self.req_id,
                        self.kvs._span_rep(leader), phase="submit")
        self.kvs.remove(leader, key, client_id=self.client_id,
                        req_id=self.req_id)
        return self.req_id

    def merge(self, leader: int, op: int, key: bytes,
              val: bytes) -> int:
        """Submit a stamped mergeable write (same exactly-once
        contract as :meth:`put` — one outstanding req per session)."""
        self.req_id += 1
        if self.kvs.history is not None:
            self.kvs.history.invoke("merge", key, val,
                                    client=self.client_id,
                                    req_id=self.req_id, replica=leader)
        spans = self.kvs._spans()
        if spans is not None:
            spans.begin(self.client_id, self.req_id,
                        self.kvs._span_rep(leader), phase="submit")
        self.kvs.merge(leader, op, key, val, client_id=self.client_id,
                       req_id=self.req_id)
        return self.req_id

    def retransmit_put(self, leader: int, key: bytes, val: bytes,
                       req_id: int) -> None:
        """Resend an earlier PUT verbatim (client saw no ack — e.g. the
        leader died after commit). Safe to call any number of times."""
        if self.kvs.history is not None:
            op_id = self.kvs.history.op_id_for(self.client_id, req_id)
            if op_id is not None:
                self.kvs.history.retransmit(op_id, replica=leader)
        spans = self.kvs._spans()
        if spans is not None:
            # same (client, req) key -> same span: a retransmit is the
            # same logical command, recorded as a retransmit mark
            spans.begin(self.client_id, req_id,
                        self.kvs._span_rep(leader), phase="submit")
        self.kvs.put(leader, key, val, client_id=self.client_id,
                     req_id=req_id)
