"""Causal command tracing — cross-replica spans, step-phase
attribution, Perfetto export.

The metrics registry answers "how is the cluster doing"; the trace
ring answers "what did the protocol do"; nothing before this module
answers the question production operation of a replicated serving
stack actually asks: *where did this one slow request spend its time?*

Three parts, all host-side, stdlib-only at import (JAX is touched only
inside the optional fencing path):

* :class:`SpanRecorder` — follows each client command end-to-end:
  session submit → proxy enqueue → leader append (stamped with
  ``(term, index)``) → quorum ack → per-replica commit advance →
  per-replica apply → client ack. Cross-replica correlation is keyed
  by ``(term, index)``: the pair is unique cluster-wide (terms are
  unique per leader by quorum election; indices are the global
  monotone, rebase-corrected log positions), so span dumps from
  different host processes merge into one causal timeline. Sampling
  is rate-limited by default (one command in
  :data:`DEFAULT_SAMPLE_EVERY`) so the hot path stays cheap — an
  unsampled command costs one counter increment; marks on unsampled
  keys are dictionary misses.

* :class:`StepPhaseProfiler` — attributes driver/daemon hot-loop wall
  time to nested phases (exact ``(count, total, max)`` sums in
  ``acc``; the seven coarse ones — host encode, device dispatch, device
  sync, quorum wait, apply, ack release, apply/replay/ack — also feed
  the histogram registry, ``step_phase_us{phase=...}``) and closes the
  account of each loop cycle with a residual. Device sync is measured via explicit
  ``jax.block_until_ready`` fencing — OFF by default, because without
  a fence the dispatch phase deliberately conflates enqueue with
  device time (the async-dispatch norm) and fencing serializes the
  pipeline; with ``fence=True`` the sync cost lands in its own
  ``device_sync`` series. Fencing changes no compiled programs
  (``tests/test_spans.py`` guards compiled-step cache keys).

  What a benchmark's probe reads of it (``perfbench``'s
  ``DriverDeployment.probe`` exports every ``acc`` key as
  ``phase.<name>.count`` / ``phase.<name>.total_us`` and every registry
  counter, summed over its labels, as ``counter.<name>``). Totals are
  INCLUSIVE; indentation is containment on the serial loop:

  ==================== ==============================================
  ``cycle``            one loop iteration (or one ticket on the
                       readback thread); never in the event ring
    ``dispatch_gate``  ``_pipeline_ready``, ``_can_idle_skip``, ``_busy``
    ``pipeline_wait``  dispatch thread waiting for the readback thread
    ``idle_wait``      parked on ``_wake`` (idle park, ``period`` wait)
    ``admin_pump``     ``_drain_admin`` + ``_pump_submitq``
    ``host_encode``    batch pack (holds ``input_transfer`` on the
                       single-step path, and ``dispatch_lock_wait``:
                       its take of the host lock, when contended)
    ``device_dispatch`` program enqueue: ``input_transfer`` (burst
                       path), ``dispatch_lock_wait`` (its own take of
                       the host lock, when contended), ``program_call``
                       (the call into the compiled program, alone,
                       inside the lock)
    ``device_sync``    ``fence=True`` only
    ``quorum_wait``    block on the step's ONE packed read, then
                       ``readback_rest``: the reads compiled only on
                       request (none in the default programs)
    ``post_readback``  audit ingest, telemetry, stamps, requeue
    ``apply``          ``replay_fetch`` [``fetch_lock_wait`` (the take
                       that binds the fetch, when contended),
                       ``fetch_enqueue`` (the fetch program's enqueue,
                       under the lock), ``fetch_read`` (both host
                       reads)], ``replay_decode`` (``decode_window``)
    ``finish_tail``    flight record, rebase, spans, leases, reads
    ``post_step_rules`` ``_post_step`` but for the two below
    ``apply_replay_ack`` ``store_append``, ``replay_send``,
                       ``replay_drain``, ``ack_release``
    ``observe``        ``_observe_step`` + the alert/health cadences
    ``profiler``       this class's bookkeeping round the above
    ``unattributed``   the rest of the cycle
  ``intake_to_ack``    per shim event (a pipelining client's read is
                       one event of many operations):
                       ``PendingEvent.t0`` -> ack release
  ``intake_queue_wait`` per shim event: ``t0`` -> the pump that
                       dispatches
  ``replay_answer_wait`` per write whose answers ``ReplayEngine._settle``
                       blocked for (all of them: sixteen where the write
                       held sixteen requests): the follower app's turn,
                       of ``replay_send`` (credited once a dispatch, no
                       ring entry)
  ==================== ==============================================

  The two lock waits are recorded by :class:`held` only when the take
  found the lock taken (``count`` = contended takes): the other loop
  thread, or intake, was inside it.

  Counters: ``readback_arrays_total`` (device-to-host reads in
  ``quorum_wait``), ``cfg_rescans_total`` (program steps whose
  full-ring config rescan branch ran: ``StepOutput.cfg_rescanned`` off
  the packed row, which reads alike on every replica of every group of
  one program, so the sharded engine counts a step once and not once a
  group; 0 while no config source is invalidated),
  ``replay_applies_total`` (calls into
  ``ReplayEngine.apply``), ``replay_followers_total`` (followers a
  dispatch replayed to: ``replay_send`` + ``replay_drain`` over it is
  the pass per follower), ``replay_reply_bytes_total`` (app output read
  off the replay sockets), ``intake_fragments_total`` and
  ``intake_payload_bytes_total`` (log entries and bytes admitted at
  intake: an operation larger than a slot is several entries),
  ``pruned_slots_total`` (the leader's ``head`` advance: slots the
  pruner gave back), ``append_clamped_total`` (entries a dispatch
  offered the leader's ring that its capacity clamp did not take; they
  are queued again), ``ring_wraps_total`` (times the leader's ``end``
  crossed a multiple of ``n_slots``), all three off the packed row the
  readback reads anyway, ``replay_requests_total`` (replayed SENDs that
  ended a request: ``replay_applies_total`` over it is the calls a
  whole request costs a follower), ``replay_order_timeouts_total``
  (``ReplayEngine.order_timeouts``: answers waited ``ORDER_WAIT_S``
  for in vain), ``replay_answers_total`` (answer lines read off a
  replay socket and matched to the write they answer: over
  ``replay_applies_total`` it is the requests a write held, 16 under
  a client that pipelines sixteen) and
  ``replay_unproven_handoffs_total`` (writes that went to another
  connection while the last one's requests were not all answered: 0
  where the log's order was held), ``fetch_rows_total`` (rows a replica each standalone
  replay fetch asked of the device: the static width it ran at; over
  ``replay_fetch``'s count it says which width serves),
  ``input_put_calls_total``, ``input_put_buffers_total`` and
  ``input_put_bytes_total`` (what the engine's one put, ``runtime/
  sim.py`` ``make_put``, started: a CALL is one host array handed
  over, by ``jnp.asarray`` or by one ``jax.device_put`` onto a mesh;
  a dispatch's arguments are ONE packed array since PR 51, and its
  replay fetch's ``starts`` one more, so 2 a dispatch on either path.
  BUFFERS are the device arrays a call made: one without a mesh, one
  a chip of the mesh with, so ``input_put_buffers_total`` over
  ``phase.device_dispatch.count`` reads 2 on one chip and 6 on a
  three-chip mesh, where six arrays a burst read 7 and 21: a put
  costs by the array and by the buffer, not by the byte. BYTES are
  the host array's ``nbytes``, the staging buffer whole),
  ``phase_stalls_total`` and
  ``phase_stall_us_total{phase}`` (a phase instance longer than
  ``TimeoutConfig.elec_timeout_low``; each also leaves one
  ``phase_stall`` event in the trace ring).

* Chrome trace-event export — :func:`to_chrome_trace` merges one or
  more span dumps (aligned on the shared
  :mod:`~rdma_paxos_tpu.obs.clock` anchor) into a Perfetto-loadable
  JSON object: one track per replica (phase marks) plus one
  critical-path track per sampled command (submit→append→quorum→
  apply→ack segments). ``python -m rdma_paxos_tpu.obs.spans`` merges
  multi-replica span files and prints the critical-path breakdown.

HARD RULE (inherited from the rest of ``obs``): nothing here may run
inside jitted/``shard_map``ped code — all call sites live in the host
control plane, and compiled-step cache keys are bit-identical with
tracing on or off.
"""

from __future__ import annotations

import argparse
import collections
import heapq
import json
import os
import sys
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

from rdma_paxos_tpu.config import TimeoutConfig
from rdma_paxos_tpu.obs.clock import anchor as clock_anchor
from rdma_paxos_tpu.obs.metrics import LATENCY_BUCKETS_US
from rdma_paxos_tpu.obs.trace import PHASE_STALL

_now_ns = time.perf_counter_ns

# ---------------------------------------------------------------------------
# span phases (the causal chain of one client command)
# ---------------------------------------------------------------------------

SUBMIT = "submit"        # client session issued the command
ENQUEUE = "enqueue"      # proxy queued it for the consensus step
APPEND = "append"        # leader appended it — stamped (term, index)
QUORUM = "quorum"        # majority acked: the LEADER's commit covers it
COMMIT = "commit"        # a replica's commit index covers it
APPLY = "apply"          # a replica's host apply covers it
ACK = "ack"              # client ack released
RETRANSMIT = "retransmit"  # the same (conn, req) was re-submitted
FAIL = "fail"            # terminal failure mark

# ordered critical-path phases (per-replica COMMIT marks are evidence,
# not client-visible latency; APPLY uses the origin replica's mark)
CP_PHASES = (SUBMIT, ENQUEUE, APPEND, QUORUM, APPLY, ACK)

# terminal statuses
OPEN = "open"            # still in flight (or never resolved)
DONE = "done"            # acked to the client
FAILOVER = "failover"    # failed at deposition / step-down / stop

DEFAULT_SAMPLE_EVERY = 64
DEFAULT_CAPACITY = 4096

# runtime override for the sampling rate: every recorder built without
# an explicit ``sample_every`` (the driver, the sharded driver, the
# RP_GOVERNOR daemon — all construct a default Observability) honors
# it. 0 disables tracing entirely; garbage falls back to the default.
SAMPLE_ENV = "RP_TRACE_SAMPLE"


def default_sample_every() -> int:
    raw = os.environ.get(SAMPLE_ENV)
    if raw is None:
        return DEFAULT_SAMPLE_EVERY
    try:
        return max(0, int(raw))
    except ValueError:
        return DEFAULT_SAMPLE_EVERY


def span_trace_id(conn: int, req: int) -> str:
    """The stable external id of one command span — what exemplars
    carry and what ``obs blame``/Perfetto label spans as."""
    return f"c{conn}/r{req}"


class _Span:
    """One sampled command's causal record (host bookkeeping only)."""

    __slots__ = ("conn", "req", "origin", "term", "index", "leader",
                 "group", "status", "retransmits", "pending_marks",
                 "events")

    def __init__(self, conn: int, req: int, origin: int):
        self.conn = conn
        self.req = req
        self.origin = origin           # replica the command entered at
        self.term: Optional[int] = None
        self.index: Optional[int] = None
        self.leader: Optional[int] = None
        self.group = -1                # consensus group (-1: unsharded)
        self.status = OPEN
        self.retransmits = 0
        # commit+apply marks still expected (2 per correlated replica);
        # a DONE span retires once they all arrive
        self.pending_marks = 0
        self.events: List[Tuple[str, int, float]] = []  # (phase, rep, ts)

    def as_dict(self) -> dict:
        d = dict(conn=self.conn, req=self.req, origin=self.origin,
                 term=self.term, index=self.index, leader=self.leader,
                 status=self.status, retransmits=self.retransmits,
                 events=[[p, r, t] for (p, r, t) in self.events])
        if self.group >= 0:
            # sharded spans carry their group; unsharded dumps keep the
            # pre-sharding schema byte-for-byte (golden-file pinned)
            d["group"] = self.group
        return d


class SpanRecorder:
    """Thread-safe, bounded, sampled recorder of command spans.

    Keys: a command is identified by ``(conn, req)`` — the driver's
    globally-unique connection id + per-replica submit sequence, or a
    KVS session's ``(client_id, req_id)`` stamp. A retransmit reuses
    the key, so it lands on the SAME span (it is the same logical
    command).

    Frontier marks are O(log open-spans) via per-replica heaps:
    ``commit_advance(r, n)`` / ``apply_advance(r, n)`` pop every
    sampled span whose stamped absolute index is below the frontier.
    Indices are ABSOLUTE (rebase-corrected): callers add their
    ``rebased_total`` so i32 rollovers never tear a span.
    """

    def __init__(self, capacity: int = DEFAULT_CAPACITY,
                 sample_every: Optional[int] = None,
                 clock=time.monotonic):
        self.capacity = capacity
        if sample_every is None:
            # resolved at construction (not import) so a test/daemon
            # that sets RP_TRACE_SAMPLE after import still wins
            sample_every = default_sample_every()
        self.sample_every = max(0, int(sample_every))  # 0 = disabled
        self._clock = clock
        self._lock = threading.Lock()
        self._counter = 0                  # commands seen (sampling)
        self._open: Dict[Tuple[int, int], _Span] = {}
        self._done: collections.deque = collections.deque(maxlen=capacity)
        # acked spans still awaiting commit/apply marks (FIFO): a
        # permanently-stopped replica's frontier never advances, so at
        # capacity the oldest of these is force-retired — the client
        # already has its ack; the missing marks ARE the evidence —
        # instead of wedging the recorder for the process lifetime
        self._done_pending: "collections.OrderedDict" = \
            collections.OrderedDict()
        self.dropped = 0                   # samples refused at capacity
        # (group, term, index) -> key, for cross-replica correlation
        # queries (group -1 = unsharded single-group callers)
        self._by_ti: Dict[Tuple[int, int, int], Tuple[int, int]] = {}
        # per-replica frontier heaps: (abs_index, key)
        self._await_commit: Dict[int, list] = {}
        self._await_apply: Dict[int, list] = {}
        # per-origin-replica ack matching: (req, key) — the driver
        # releases acks by monotone submit sequence
        self._await_ack: Dict[int, list] = {}
        # cheap read-span variant (runtime/reads.py): completed
        # lease/read-index reads as (replica, path, t0, t1) records —
        # no correlation machinery, own sampling counter so read
        # traffic can never shift which COMMANDS get sampled
        self._reads: collections.deque = collections.deque(
            maxlen=capacity)
        self._read_counter = 0

    # ---------------- cheap-path predicates ----------------

    @property
    def enabled(self) -> bool:
        return self.sample_every > 0

    @property
    def open_count(self) -> int:
        return len(self._open)

    def set_sample_every(self, n: int) -> None:
        """1 = trace every command (``--trace``); 0 = off."""
        self.sample_every = max(0, int(n))

    def resize(self, capacity: int) -> None:
        """Grow/shrink the retained-span bound (``--trace`` runs size
        it to the whole workload so the export misses nothing)."""
        with self._lock:
            self.capacity = int(capacity)
            self._done = collections.deque(self._done,
                                           maxlen=self.capacity)

    # ---------------- recording ----------------

    def begin(self, conn: int, req: int, replica: int,
              phase: str = ENQUEUE) -> bool:
        """A command entered the system; returns True iff sampled.
        Re-entering an already-open key records a retransmit on the
        existing span (same logical command)."""
        if not self.sample_every:
            return False
        with self._lock:
            key = (conn, req)
            sp = self._open.get(key)
            if sp is not None:
                sp.retransmits += 1
                sp.events.append((RETRANSMIT, replica, self._clock()))
                return True
            self._counter += 1
            if (self._counter - 1) % self.sample_every:
                return False
            if len(self._open) >= self.capacity:
                if self._done_pending:
                    # evict the oldest acked-but-unmarked span rather
                    # than refusing every future sample
                    old_key, _ = self._done_pending.popitem(last=False)
                    old_sp = self._open.get(old_key)
                    if old_sp is not None:
                        self._retire_locked(old_key, old_sp)
                else:
                    self.dropped += 1
                    return False
            sp = _Span(conn, req, replica)
            sp.events.append((phase, replica, self._clock()))
            self._open[key] = sp
            h = self._await_ack.setdefault(replica, [])
            heapq.heappush(h, (req, key))
            if len(h) > 4 * self.capacity:
                self._compact_locked(h)     # direct-key acks bypass it
            return True

    def mark(self, conn: int, req: int, phase: str,
             replica: int = -1) -> None:
        """Stamp a phase on an open sampled span (no-op otherwise)."""
        if not self._open:
            return
        with self._lock:
            sp = self._open.get((conn, req))
            if sp is not None:
                sp.events.append((phase, replica, self._clock()))

    def stamp_append(self, conn: int, req: int, term: int, index: int,
                     leader: int,
                     replicas: Sequence[int] = (),
                     group: int = -1) -> None:
        """The leader appended this command at absolute ``index`` in
        ``term`` — the cross-replica correlation key. ``replicas``
        lists the replica ids whose commit/apply frontiers this
        process observes (all of them in-process; just the local one
        for a NodeDaemon); the span retires once each has both marks
        (plus the client ack). A second append of the same key (a
        committed duplicate from a retransmit) is recorded but the
        FIRST (term, index) wins — first-commit order is the one the
        state machine deduplicates to.

        ``group`` namespaces the correlation key for sharded clusters:
        ``(term, index)`` is unique within ONE consensus group but G
        independent groups number terms and indices identically, so
        the full key is ``(group, term, index)`` (-1 for unsharded
        callers — the legacy key, unchanged)."""
        if not self._open:
            return
        with self._lock:
            sp = self._open.get((conn, req))
            if sp is None:
                return
            ts = self._clock()
            if sp.term is not None:
                sp.retransmits += 1
                sp.events.append((RETRANSMIT, leader, ts))
                return
            sp.term, sp.index, sp.leader = int(term), int(index), leader
            sp.group = int(group)
            sp.events.append((APPEND, leader, ts))
            key = (conn, req)
            self._by_ti[(sp.group, sp.term, sp.index)] = key
            sp.pending_marks = 2 * len(replicas)
            for r in replicas:
                hc = self._await_commit.setdefault(r, [])
                ha = self._await_apply.setdefault(r, [])
                heapq.heappush(hc, (sp.index, key))
                heapq.heappush(ha, (sp.index, key))
                if len(hc) > 4 * self.capacity:
                    # a frontier that never advances (partitioned
                    # replica) must not accumulate retired spans' stale
                    # entries without bound
                    self._compact_locked(hc)
                    self._compact_locked(ha)

    def _compact_locked(self, heap: list) -> None:
        live = [(i, k) for (i, k) in heap if k in self._open]
        heapq.heapify(live)
        heap[:] = live

    def _frontier(self, heaps: Dict[int, list], replica: int,
                  upto: int, phase: str) -> None:
        h = heaps.get(replica)
        if not h:
            return
        with self._lock:
            ts = self._clock()
            while h and h[0][0] < upto:
                idx, key = heapq.heappop(h)
                sp = self._open.get(key)
                if sp is None or sp.index != idx:
                    continue               # retired / superseded entry
                sp.events.append((phase, replica, ts))
                if phase == COMMIT and replica == sp.leader:
                    # the leader's commit advance IS the quorum ack
                    sp.events.append((QUORUM, replica, ts))
                sp.pending_marks -= 1
                if sp.pending_marks <= 0 and sp.status == DONE:
                    self._retire_locked(key, sp)

    def commit_advance(self, replica: int, upto: int) -> None:
        """Replica ``replica``'s commit frontier reached ``upto``
        (absolute count: indices < upto are committed)."""
        self._frontier(self._await_commit, replica, upto, COMMIT)

    def apply_advance(self, replica: int, upto: int) -> None:
        self._frontier(self._await_apply, replica, upto, APPLY)

    def ack_release(self, replica: int,
                    upto_req: int) -> List[Tuple[int, int]]:
        """The driver released client acks on ``replica`` for every
        submit sequence <= ``upto_req``. Returns the ``(conn, req)``
        keys of the SAMPLED spans acked by this call — the driver's
        latency observe attaches histogram exemplars only to those."""
        h = self._await_ack.get(replica)
        if not h:
            return []
        acked: List[Tuple[int, int]] = []
        with self._lock:
            ts = self._clock()
            while h and h[0][0] <= upto_req:
                req, key = heapq.heappop(h)
                sp = self._open.get(key)
                if sp is None:
                    continue
                sp.events.append((ACK, replica, ts))
                sp.status = DONE
                acked.append(key)
                if sp.pending_marks <= 0:
                    self._retire_locked(key, sp)
                else:
                    self._done_pending[key] = None
        return acked

    def ack_key(self, conn: int, req: int) -> None:
        """Direct-key client ack (KVS sessions, which observe commit
        through the dedup high-water mark rather than a driver seq)."""
        if not self._open:
            return
        with self._lock:
            key = (conn, req)
            sp = self._open.get(key)
            if sp is None:
                return
            sp.events.append((ACK, sp.origin, self._clock()))
            sp.status = DONE
            if sp.pending_marks <= 0:
                self._retire_locked(key, sp)
            else:
                self._done_pending[key] = None

    def fail_open(self, replica: int, status: str = FAILOVER) -> int:
        """Close EVERY open span awaiting ack on ``replica`` with a
        terminal ``status`` — the leader-failover path: when the
        driver fails its inflight waiters (deposition, step-down,
        stop), their spans must terminate too, never leak. Returns the
        number closed."""
        h = self._await_ack.get(replica)
        if not h:
            return 0
        n = 0
        with self._lock:
            ts = self._clock()
            while h:
                _, key = heapq.heappop(h)
                sp = self._open.get(key)
                if sp is None:
                    continue
                sp.events.append((FAIL, replica, ts))
                sp.status = status
                self._retire_locked(key, sp)
                n += 1
        return n

    def fail_key(self, conn: int, req: int, status: str = FAILOVER,
                 replica: int = -1) -> None:
        if not self._open:
            return
        with self._lock:
            key = (conn, req)
            sp = self._open.get(key)
            if sp is None:
                return
            sp.events.append((FAIL, replica, self._clock()))
            sp.status = status
            self._retire_locked(key, sp)

    def _retire_locked(self, key, sp: _Span) -> None:
        self._open.pop(key, None)
        self._done_pending.pop(key, None)
        if sp.term is not None:
            self._by_ti.pop((sp.group, sp.term, sp.index), None)
        self._done.append(sp)

    # ---------------- queries / export ----------------

    def read_span(self, replica: int, path: str, t0: float, *,
                  group: int = -1, status: str = DONE) -> Optional[str]:
        """Record one served linearizable READ as a lightweight span
        (sampled like commands, but on a separate counter): the read
        critical path is just [enqueue, serve] on the serving replica
        — no append/commit/apply correlation to carry. Rendered as
        duration slices on a dedicated reads track by
        :func:`to_chrome_trace`. Returns the read's trace id when
        sampled (truthy, so pre-existing boolean callers still work),
        None otherwise — the id feeds the read-latency histogram's
        exemplar."""
        if not self.sample_every:
            return None
        with self._lock:
            self._read_counter += 1
            if (self._read_counter - 1) % self.sample_every:
                return None
            rid = f"read-{self._read_counter - 1}"
            self._reads.append(dict(replica=int(replica), path=path,
                                    t0=float(t0), t1=self._clock(),
                                    group=int(group), status=status,
                                    id=rid))
            return rid

    def key_for(self, term: int, index: int,
                group: int = -1) -> Optional[Tuple[int, int]]:
        with self._lock:
            return self._by_ti.get((int(group), int(term), int(index)))

    def counts(self) -> dict:
        with self._lock:
            by_status: Dict[str, int] = {}
            for sp in self._done:
                by_status[sp.status] = by_status.get(sp.status, 0) + 1
            return dict(open=len(self._open), done=len(self._done),
                        dropped=self.dropped, sampled=by_status)

    def dump(self, anchor: Optional[dict] = None) -> dict:
        """Point-in-time span dump: plain data, JSON-serializable,
        stamped with the shared clock anchor so multi-process dumps
        align on one timebase. Open spans are included as-is (status
        ``open``)."""
        with self._lock:
            spans = ([sp.as_dict() for sp in self._done]
                     + [sp.as_dict() for sp in self._open.values()])
            reads = [dict(r) for r in self._reads]
        out = dict(schema=1,
                   anchor=anchor if anchor is not None else clock_anchor(),
                   sample_every=self.sample_every,
                   dropped=self.dropped, spans=spans)
        if reads:
            # only when read spans exist: dumps from read-free runs
            # keep the pre-reads schema byte-for-byte (golden-pinned)
            out["reads"] = reads
        return out

    def write_json(self, path: str) -> str:
        import os
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(self.dump(), f, indent=2)
        os.replace(tmp, path)
        return path

    def reset(self) -> None:
        with self._lock:
            self._open.clear()
            self._done.clear()
            self._by_ti.clear()
            self._await_commit.clear()
            self._await_apply.clear()
            self._await_ack.clear()
            self._done_pending.clear()
            self._reads.clear()
            self._read_counter = 0
            self._counter = 0
            self.dropped = 0


def active_recorder(obs) -> Optional[SpanRecorder]:
    """The facade's span recorder iff tracing is enabled — the ONE
    enablement probe every integration point (sim, KVS, ...) shares,
    so the predicate can never diverge between paths."""
    if obs is None:
        return None
    sp = getattr(obs, "spans", None)
    return sp if (sp is not None and sp.enabled) else None


# ---------------------------------------------------------------------------
# step-phase profiler
# ---------------------------------------------------------------------------

# the attributable hot-loop phases. The first seven are the coarse
# account (one ``step_phase_us{phase=}`` histogram each); the rest
# split them and name what lay between them, and live only in the exact
# ``acc`` sums and the event ring (a histogram is 18 series in the
# 0.25 s sampling store: the ``observe`` phase would pay for its own
# detail).
PHASE_HOST_ENCODE = "host_encode"        # batch pack / input build
PHASE_DEVICE_DISPATCH = "device_dispatch"  # program enqueue (async)
PHASE_DEVICE_SYNC = "device_sync"        # explicit fence (opt-in)
PHASE_QUORUM_WAIT = "quorum_wait"        # blocking commit readback
PHASE_APPLY = "apply"                    # committed-window replay
PHASE_ACK_RELEASE = "ack_release"        # waiter release + latency obs
PHASE_APPLY_REPLAY_ACK = "apply_replay_ack"  # driver store/replay/ack
                                         # sweep (whole-batch, per
                                         # replica) — the host_path
                                         # A/B attribution phase
PHASE_CYCLE = "cycle"                    # one _dispatch_loop iteration
PHASE_UNATTRIBUTED = "unattributed"      # cycle minus its direct children
PHASE_PROFILER = "profiler"              # of a cycle: start/stop bookkeeping
                                         # round its direct children
PHASE_ADMIN_PUMP = "admin_pump"          # _drain_admin + _pump_submitq
PHASE_DISPATCH_GATE = "dispatch_gate"    # _pipeline_ready / drain /
                                         # _can_idle_skip / _busy
PHASE_IDLE_WAIT = "idle_wait"            # parked on _wake
PHASE_PIPELINE_WAIT = "pipeline_wait"    # dispatch thread waits for the
                                         # readback thread (drain / depth)
PHASE_INPUT_TRANSFER = "input_transfer"  # jnp.asarray of step inputs
PHASE_PROGRAM_CALL = "program_call"      # fn(state, *args) alone, in the lock
# a CONTENDED take of the engine's host lock (``held``): an uncontended
# one reads no clock and leaves no sample
PHASE_DISPATCH_LOCK_WAIT = "dispatch_lock_wait"  # begin_step / begin_burst
PHASE_FETCH_LOCK_WAIT = "fetch_lock_wait"  # the take that binds the fetch
PHASE_FETCH_ENQUEUE = "fetch_enqueue"    # _fetch_all(...) under the lock
PHASE_FETCH_READ = "fetch_read"          # both np.asarray of the fetch
PHASE_READBACK_REST = "readback_rest"    # reads after the packed one
PHASE_POST_READBACK = "post_readback"    # finish: quorum_wait -> apply
PHASE_REPLAY_FETCH = "replay_fetch"      # fetch bind + both host reads
PHASE_REPLAY_DECODE = "replay_decode"    # decode_window
PHASE_FINISH_TAIL = "finish_tail"        # finish: after apply
PHASE_STORE_APPEND = "store_append"      # framed blobs -> StableStore
PHASE_REPLAY_SEND = "replay_send"        # ReplayEngine.apply loop
PHASE_REPLAY_DRAIN = "replay_drain"      # ReplayEngine.drain_responses
PHASE_POST_STEP_RULES = "post_step_rules"  # _post_step minus apply/observe
PHASE_OBSERVE = "observe"                # _observe_step + cadences
# per operation, credited (no start/stop, no ring entry)
OP_INTAKE_TO_ACK = "intake_to_ack"       # PendingEvent.t0 -> release
OP_INTAKE_QUEUE_WAIT = "intake_queue_wait"  # PendingEvent.t0 -> pump
# per write whose answers a ReplayEngine blocked for, credited once a
# dispatch
OP_REPLAY_ANSWER_WAIT = "replay_answer_wait"  # _settle's blocking recvs
# a membership change and a replica's recovery: rare, recorded only when
# they run. The first two nest where they run (admin_pump, or
# post_step_rules for the auto-recovery); the last two span many cycles
# and are credited whole when they end, one sample each
PHASE_CHECKPOINT = "checkpoint"          # _do_checkpoint
PHASE_RECOVER = "recover"                # snapshot take/verify/install +
#                                          the store's transfer
SPAN_CONFIG_CHANGE = "config_change"     # TRANSIT submitted -> STABLE seen
SPAN_APP_REBUILD = "app_rebuild"         # a fresh app fed the history


class StepPhaseProfiler:
    """Wall-time phase attribution for the driver/daemon hot loops.

    Without fencing (the default), ``device_dispatch`` measures program
    ENQUEUE under async dispatch and the device time surfaces wherever
    the host first blocks on results (``quorum_wait``) — the honest
    shape of a pipelined driver, and exactly what the pre-existing
    ``step_latency_us`` conflated. With ``fence=True``, :meth:`sync`
    blocks on the step's outputs immediately after dispatch, so device
    time lands in its own ``device_sync`` series and ``quorum_wait``
    shrinks to the readback. Fencing serializes the dispatch pipeline —
    it is a profiling mode, off by default, and changes no compiled
    programs (cache-key guarded).

    Phases NEST, per thread: ``start`` pushes on the calling thread's
    stack, ``stop`` pops, and every total in ``acc`` is INCLUSIVE of
    the phases nested in it. ``cycle`` is the one container (one
    iteration of the dispatch loop, or one ticket's finish and post-step
    on the readback thread of the pipelined loop): at its stop, ``profiler`` is credited with this class's own bookkeeping
    round the cycle's direct children and ``unattributed`` with the
    rest of the cycle that no direct child covers, so direct children
    + ``profiler`` + ``unattributed`` = ``cycle``; it never enters the
    event ring (a span over everything would name every device-idle
    gap), nor does ``pipeline_wait``, which spans the other thread's
    whole cycle. Two deep go ``device_dispatch`` > ``program_call`` and
    ``apply`` > ``replay_fetch`` > ``fetch_enqueue`` / ``fetch_read``;
    a take of the engine's host lock that had to wait (:meth:`acquire`,
    through :class:`held`) is ``dispatch_lock_wait`` or
    ``fetch_lock_wait`` inside the phase that took it, a wait by design
    like ``pipeline_wait`` but in the ring. A phase instance
    that runs (waits apart) longer than :attr:`STALL_US` leaves one
    ``phase_stall`` trace event (innermost phase only) and adds to ``phase_stalls_total`` /
    ``phase_stall_us_total{phase}``.
    """

    BUCKETS_US = LATENCY_BUCKETS_US
    PHASES = (PHASE_HOST_ENCODE, PHASE_DEVICE_DISPATCH,
              PHASE_DEVICE_SYNC, PHASE_QUORUM_WAIT, PHASE_APPLY,
              PHASE_ACK_RELEASE, PHASE_APPLY_REPLAY_ACK)
    _HISTOGRAMS = frozenset(PHASES)
    DETAIL = (PHASE_CYCLE, PHASE_UNATTRIBUTED, PHASE_PROFILER,
              PHASE_ADMIN_PUMP, PHASE_DISPATCH_GATE, PHASE_IDLE_WAIT,
              PHASE_PIPELINE_WAIT, PHASE_INPUT_TRANSFER,
              PHASE_PROGRAM_CALL, PHASE_DISPATCH_LOCK_WAIT,
              PHASE_READBACK_REST, PHASE_POST_READBACK,
              PHASE_REPLAY_FETCH, PHASE_FETCH_LOCK_WAIT,
              PHASE_FETCH_ENQUEUE, PHASE_FETCH_READ,
              PHASE_REPLAY_DECODE, PHASE_FINISH_TAIL,
              PHASE_STORE_APPEND, PHASE_REPLAY_SEND, PHASE_REPLAY_DRAIN,
              PHASE_POST_STEP_RULES, PHASE_OBSERVE, OP_INTAKE_TO_ACK,
              OP_INTAKE_QUEUE_WAIT, OP_REPLAY_ANSWER_WAIT,
              PHASE_CHECKPOINT, PHASE_RECOVER,
              SPAN_CONFIG_CHANGE, SPAN_APP_REBUILD)
    COUNTERS = ("readback_arrays_total", "cfg_rescans_total",
                "replay_applies_total",
                "replay_followers_total", "replay_reply_bytes_total",
                "intake_fragments_total", "intake_payload_bytes_total",
                "recover_bytes_total", "recover_entries_total",
                "replay_reconnects_total", "pruned_slots_total",
                "append_clamped_total", "ring_wraps_total",
                "replay_requests_total", "replay_order_timeouts_total",
                "replay_answers_total", "replay_unproven_handoffs_total",
                "fetch_rows_total", "input_put_calls_total",
                "input_put_bytes_total", "input_put_buffers_total")
    # a thread waiting by design: its length counts towards no stall,
    # its own or of the phase it waits in
    WAITS = (PHASE_IDLE_WAIT, PHASE_PIPELINE_WAIT,
             PHASE_DISPATCH_LOCK_WAIT, PHASE_FETCH_LOCK_WAIT)
    # spans over (nearly) everything another thread does: in the event
    # ring they would name every device-idle gap
    NOT_IN_RING = (PHASE_CYCLE, PHASE_PIPELINE_WAIT)
    # the length at which a stall of the loop starts costing elections
    STALL_US = TimeoutConfig().elec_timeout_low * 1e6

    def __init__(self, metrics=None, *, fence: bool = False,
                 replica: int = -1, trace=None, step_index=None):
        self.metrics = metrics           # MetricsRegistry or None
        self.fence = fence
        self.replica = replica
        self.trace = trace               # obs.trace.TraceRing or None
        self.step_index = step_index     # () -> int, stamps stall events
        # every key exists from the start: a reader's dict(acc) never
        # sees the dict change size, and a phase that never ran reads 0
        self.acc: Dict[str, Tuple[int, float, float]] = {
            p: (0, 0.0, 0.0) for p in self.PHASES + self.DETAIL}
        self._lock = threading.Lock()    # acc read-modify-write
        self._tls = threading.local()    # .stack: one per thread
        # opt-in timestamped phase slices (enable_events): the
        # host-phase TRACK of the merged device timeline
        # (obs.device.merge_timeline) — histograms alone cannot place
        # a phase on a wall-clock axis
        self.events: Optional[collections.deque] = None
        if metrics is not None:
            for name in self.COUNTERS:
                metrics.inc(name, 0)
            metrics.inc("phase_stalls_total", 0)
            metrics.inc("phase_stall_us_total", 0, phase=PHASE_CYCLE)

    def enable_events(self, capacity: int = 65536) -> None:
        """Record ``(phase, t0_monotonic, t1_monotonic)`` triples in a
        bounded ring alongside the histograms (off by default — one
        extra clock read per stop)."""
        self.events = collections.deque(maxlen=capacity)

    def _stack(self) -> list:
        try:
            return self._tls.stack
        except AttributeError:
            self._tls.stack = []
            return self._tls.stack

    def start(self, phase: str) -> None:
        t0 = _now_ns()
        try:
            stack = self._tls.stack
        except AttributeError:           # this thread's first phase
            stack = self._tls.stack = []
        for i, frame in enumerate(stack):
            if frame[0] == phase:
                # no phase nests in itself: this one was abandoned by
                # an exception between its start and stop
                del stack[i:]
                break
        # [phase, t0, a child already stalled, ns spent waiting by
        #  design (WAITS), and for a cycle: its direct children's ns
        #  with their stop() bookkeeping, of which bookkeeping]
        stack.append([phase, t0, False, 0, 0, 0])

    def stop(self, phase: str) -> None:
        now = _now_ns()
        stack = self._stack()
        i = len(stack) - 1
        while i >= 0 and stack[i][0] != phase:
            i -= 1
        if i < 0:
            return                       # never started: as before
        _, t0, child_stalled, wait_ns, child_ns, own_ns = stack[i]
        del stack[i:]                    # frames above it were abandoned
        ns = now - t0
        us = ns / 1e3
        self.credit(phase, us)
        if phase == PHASE_CYCLE:
            # the account closes: named children + this method's own
            # adds round them + the residual
            self.credit(PHASE_PROFILER, own_ns / 1e3)
            self.credit(PHASE_UNATTRIBUTED, max(ns - child_ns, 0) / 1e3)
        if self.events is not None and phase not in self.NOT_IN_RING:
            t1m = time.monotonic()
            self.events.append((phase, t1m - us / 1e6, t1m))
        if phase in self.WAITS:
            wait_ns = ns                 # waiting is no stall
        if stack:
            stack[-1][3] += wait_ns
        if ns - wait_ns >= self.STALL_US * 1e3:
            if stack:
                stack[-1][2] = True
            if not child_stalled:        # a parent's stall is its child's
                self._stall(phase, (ns - wait_ns) / 1e3)
        if self.metrics is not None and phase in self._HISTOGRAMS:
            self.metrics.observe("step_phase_us", us,
                                 buckets=self.BUCKETS_US, phase=phase,
                                 replica=self.replica)
        if len(stack) == 1 and stack[0][0] == PHASE_CYCLE:
            # seen from its cycle this phase lasted until here: what
            # that exceeds ``ns`` by is the profiler's own cost, never
            # the cycle's residual
            span = _now_ns() - t0
            stack[0][4] += span
            stack[0][5] += span - ns

    def acquire(self, lock, phase: str) -> None:
        """Take ``lock``; only a take that finds it taken is a sample
        of ``phase`` (one of :attr:`WAITS`), so an uncontended take
        reads no clock and ``acc[phase]``'s count is the number of
        contended ones."""
        if lock.acquire(False):
            return
        self.start(phase)
        lock.acquire()
        self.stop(phase)

    def credit(self, name: str, total_us: float, n: int = 1) -> None:
        """Add ``n`` samples totalling ``total_us`` to ``acc[name]``
        with no clock read and no ring entry: how a per-operation sum
        (``intake_to_ack``) costs one add an operation."""
        with self._lock:
            cnt, tot, mx = self.acc.get(name, (0, 0.0, 0.0))
            self.acc[name] = (cnt + n, tot + total_us,
                              max(mx, total_us / n))

    def count(self, counter: str, n: int) -> None:
        """Bump one of :attr:`COUNTERS` (work done inside a phase)."""
        if self.metrics is not None:
            self.metrics.inc(counter, n)

    def _stall(self, phase: str, us: float) -> None:
        if self.metrics is not None:
            self.metrics.inc("phase_stalls_total")
            self.metrics.inc("phase_stall_us_total", us, phase=phase)
        if self.trace is not None:
            self.trace.record(
                PHASE_STALL, phase=phase, us=round(us, 1),
                step=(int(self.step_index())
                      if self.step_index is not None else -1))

    def sync(self, outputs) -> None:
        """Explicit device fence: block until ``outputs`` are ready,
        timed as ``device_sync``. NO-OP unless fencing is enabled —
        the default path never blocks here (and never imports JAX)."""
        if not self.fence:
            return
        import jax                        # deliberate lazy import
        self.start(PHASE_DEVICE_SYNC)
        jax.block_until_ready(outputs)
        self.stop(PHASE_DEVICE_SYNC)

    def sums(self) -> Dict[str, dict]:
        """THE public read: per-phase ``{n, total_us, max_us}``,
        inclusive of nested phases, with zero-sample phases SUPPRESSED
        — benches embed it in their detail rows, so A/B tables never
        carry dead columns (e.g. a ``device_sync`` row when ``fence=``
        is off). ``acc`` itself stays readable for the benchmark's
        probe, which wants the zero rows."""
        with self._lock:
            acc = dict(self.acc)
        return {p: dict(n=a[0], total_us=round(a[1], 1),
                        max_us=round(a[2], 1))
                for p, a in sorted(acc.items()) if a[0] > 0}

    def report(self) -> str:
        return "\n".join(
            f"{phase}: n={s['n']} mean={s['total_us'] / s['n']:.1f}us "
            f"max={s['max_us']:.1f}us"
            for phase, s in self.sums().items())


class held:
    """``with held(prof, lock, phase):`` is ``with lock:`` whose take,
    when it has to wait, is recorded as ``phase``
    (:meth:`StepPhaseProfiler.acquire`); ``prof`` may be None. The one
    way the engines take their host lock on the loop threads' paths;
    the lock-discipline pass reads the lock off the call's arguments."""

    __slots__ = ("_prof", "_lock", "_phase")

    def __init__(self, prof: Optional[StepPhaseProfiler], lock,
                 phase: str):
        self._prof, self._lock, self._phase = prof, lock, phase

    def __enter__(self) -> None:
        if self._prof is None:
            self._lock.acquire()
        else:
            self._prof.acquire(self._lock, self._phase)

    def __exit__(self, *exc) -> None:
        self._lock.release()


# ---------------------------------------------------------------------------
# Chrome trace-event export (Perfetto-loadable)
# ---------------------------------------------------------------------------

CP_PID = 9999            # the critical-path pseudo-process
READS_PID = 9998         # the lease/read-index read-span pseudo-process


def _span_label(sp: dict) -> str:
    label = "c%d/r%d" % (sp["conn"], sp["req"])
    if sp.get("term") is not None:
        label += " (t%d,i%d)" % (sp["term"], sp["index"])
    return label


def _critical_path(sp: dict, wall) -> List[Tuple[str, float, float]]:
    """-> ordered (segment, t0_wall, t1_wall) list for one span: the
    client-visible chain over whichever CP phases were observed."""
    marks: Dict[str, float] = {}
    for phase, rep, ts in sp["events"]:
        if phase not in CP_PHASES:
            continue
        if phase == APPLY and rep != sp["origin"] and APPLY in marks:
            continue                      # prefer the origin's apply
        if phase in marks and phase != APPLY:
            continue                      # first mark wins
        marks[phase] = wall(ts)
    chain = [(p, marks[p]) for p in CP_PHASES if p in marks]
    return [(f"{a}->{b}", ta, tb)
            for (a, ta), (b, tb) in zip(chain, chain[1:])]


def to_chrome_trace(dumps, *, max_cp_tracks: int = 512,
                    t0_wall: Optional[float] = None) -> dict:
    """Merge one or more span dumps into a Chrome trace-event JSON
    object (Perfetto-loadable): per-replica tracks carry instant
    phase marks correlated by ``(term, index)``; each sampled command
    additionally gets a critical-path track of duration slices.
    Dumps from different processes are aligned via their stamped
    clock anchors. ``t0_wall`` overrides the computed timeline epoch —
    the hook ``obs.device.merge_timeline`` uses to fold host-phase and
    device-profiler tracks onto the SAME axis (and the only caller for
    which the epoch lands in ``otherData``)."""
    if isinstance(dumps, dict):
        dumps = [dumps]
    walls: List[float] = []
    prepared = []
    for d in dumps:
        a = d["anchor"]

        def wall(ts, _a=a):
            return _a["wall"] + (ts - _a["monotonic"])

        for sp in d["spans"]:
            walls.extend(wall(ts) for _, _, ts in sp["events"])
        for rd in d.get("reads", ()):
            walls.append(wall(rd["t0"]))
        prepared.append((d, wall))
    t0 = (t0_wall if t0_wall is not None
          else (min(walls) if walls else 0.0))

    def us(w):
        return round((w - t0) * 1e6, 3)

    events: List[dict] = []
    replicas_seen = set()
    cp_tid = 0
    for d, wall in prepared:
        for sp in d["spans"]:
            label = _span_label(sp)
            args = dict(conn=sp["conn"], req=sp["req"],
                        origin=sp["origin"], term=sp.get("term"),
                        index=sp.get("index"), status=sp["status"],
                        retransmits=sp.get("retransmits", 0))
            for phase, rep, ts in sp["events"]:
                pid = rep if rep >= 0 else sp["origin"]
                replicas_seen.add(pid)
                events.append(dict(
                    name=f"{phase} {label}", ph="i", s="p",
                    ts=us(wall(ts)), pid=pid, tid=0, args=args))
            if cp_tid < max_cp_tracks:
                segs = _critical_path(sp, wall)
                if segs:
                    cp_tid += 1
                    events.append(dict(
                        name="thread_name", ph="M", pid=CP_PID,
                        tid=cp_tid, args=dict(name=label)))
                    for seg, ta, tb in segs:
                        events.append(dict(
                            name=seg, ph="X", ts=us(ta),
                            dur=round(max(tb - ta, 0.0) * 1e6, 3),
                            pid=CP_PID, tid=cp_tid, args=args))
    n_reads = 0
    for d, wall in prepared:
        for rd in d.get("reads", ()):
            # the read critical path is one slice: enqueue→serve on
            # the serving replica's reads track
            n_reads += 1
            ta, tb = wall(rd["t0"]), wall(rd["t1"])
            events.append(dict(
                name=f"read:{rd['path']}", ph="X", ts=us(ta),
                dur=round(max(tb - ta, 0.0) * 1e6, 3),
                pid=READS_PID, tid=rd["replica"],
                args=dict(replica=rd["replica"], path=rd["path"],
                          group=rd.get("group", -1),
                          status=rd.get("status"))))
    meta = [dict(name="process_name", ph="M", pid=r, tid=0,
                 args=dict(name=f"replica {r}"))
            for r in sorted(replicas_seen)]
    meta.append(dict(name="process_name", ph="M", pid=CP_PID, tid=0,
                     args=dict(name="critical path")))
    if n_reads:
        meta.append(dict(name="process_name", ph="M", pid=READS_PID,
                         tid=0, args=dict(name="reads")))
    other = dict(tool="rdma_paxos_tpu.obs.spans",
                 dumps=len(prepared),
                 spans=sum(len(d["spans"]) for d, _ in prepared))
    if t0_wall is not None:
        # only explicit-epoch callers carry it: the default export
        # stays byte-identical (golden-file pinned)
        other["t0_wall"] = t0
    return dict(traceEvents=meta + events, displayTimeUnit="ms",
                otherData=other)


# ---------------------------------------------------------------------------
# critical-path breakdown
# ---------------------------------------------------------------------------

def breakdown(dumps) -> dict:
    """Aggregate critical-path segment durations over every span in
    ``dumps``: per segment n/mean/p50/p95/p99 µs, plus span status
    counts — the "where did the time go" table."""
    if isinstance(dumps, dict):
        dumps = [dumps]
    segs: Dict[str, List[float]] = {}
    status: Dict[str, int] = {}
    for d in dumps:
        a = d["anchor"]

        def wall(ts, _a=a):
            return _a["wall"] + (ts - _a["monotonic"])

        for sp in d["spans"]:
            status[sp["status"]] = status.get(sp["status"], 0) + 1
            for seg, ta, tb in _critical_path(sp, wall):
                segs.setdefault(seg, []).append((tb - ta) * 1e6)
    out = dict(spans=status, segments={})
    for seg, vals in segs.items():
        vals.sort()
        n = len(vals)
        out["segments"][seg] = dict(
            n=n, mean_us=round(sum(vals) / n, 2),
            p50_us=round(vals[n // 2], 2),
            p95_us=round(vals[int(n * .95)], 2),
            p99_us=round(vals[min(int(n * .99), n - 1)], 2))
    return out


def format_breakdown(bd: dict) -> str:
    lines = ["spans: " + ", ".join(f"{k}={v}"
                                   for k, v in sorted(bd["spans"].items()))]
    order = [f"{a}->{b}" for a, b in zip(CP_PHASES, CP_PHASES[1:])]
    segs = bd["segments"]
    width = max([len(s) for s in segs] or [8])
    lines.append(f"{'segment'.ljust(width)}  {'n':>7} {'mean_us':>10} "
                 f"{'p50_us':>10} {'p95_us':>10} {'p99_us':>10}")
    for seg in sorted(segs, key=lambda s: (order.index(s)
                                           if s in order else 99, s)):
        st = segs[seg]
        lines.append(f"{seg.ljust(width)}  {st['n']:>7} "
                     f"{st['mean_us']:>10.2f} {st['p50_us']:>10.2f} "
                     f"{st['p95_us']:>10.2f} {st['p99_us']:>10.2f}")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# CLI: dump / merge / report
# ---------------------------------------------------------------------------

def _load_dumps(paths: Sequence[str]) -> List[dict]:
    dumps = []
    for p in paths:
        with open(p) as f:
            doc = json.load(f)
        if "spans" not in doc:
            raise SystemExit(f"{p}: not a span dump (no 'spans' key)")
        dumps.append(doc)
    return dumps


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m rdma_paxos_tpu.obs.spans",
        description="Merge span dumps into a Perfetto-loadable Chrome "
                    "trace and print critical-path breakdowns.")
    sub = ap.add_subparsers(dest="cmd", required=True)
    for name, doc in (("merge", "merge one or more (multi-replica) raw "
                                "span dumps into ONE Chrome trace-event "
                                "JSON, aligned on the shared clock "
                                "anchors — open the output in "
                                "https://ui.perfetto.dev"),
                      ("dump", "alias of merge (single-file convert)")):
        p = sub.add_parser(name, help=doc)
        p.add_argument("files", nargs="+", help="raw span dump JSONs")
        p.add_argument("-o", "--out", required=True,
                       help="Chrome trace JSON output path")
    rp = sub.add_parser("report", help="print the aggregated "
                        "critical-path breakdown of span dumps")
    rp.add_argument("files", nargs="+")
    args = ap.parse_args(argv)

    dumps = _load_dumps(args.files)
    if args.cmd in ("merge", "dump"):
        trace = to_chrome_trace(dumps)
        with open(args.out, "w") as f:
            json.dump(trace, f)
        n = trace["otherData"]["spans"]
        print(f"wrote {args.out}: {len(trace['traceEvents'])} events "
              f"from {n} spans across {len(dumps)} dump(s) — load it "
              f"in https://ui.perfetto.dev")
    else:
        print(format_breakdown(breakdown(dumps)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
