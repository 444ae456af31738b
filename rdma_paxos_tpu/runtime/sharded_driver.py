"""ShardedClusterDriver — the e2e data plane over G consensus groups.

``ClusterDriver`` serves one consensus group: every client session rides
the single leader. This driver serves a :class:`~rdma_paxos_tpu.shard.
cluster.ShardedCluster` through the SAME polling/pipelining loop — the
multi-group scaling ``benchmarks/shard_bench.py`` demonstrates in sim,
threaded through the real proxy/shim/app path:

  * **Every replica is a serving front-end.** Clients connect to any
    replica's app; the shim events flow into that replica's proxy as
    usual. There is no single cluster leader — each of the G groups
    elects its own, spread across the R replicas.
  * **Connections are routed by key prefix.** A shim connection is
    pinned to the consensus group that owns the KEY PREFIX of its first
    replicated SEND (``KeyRouter.group_of``; the prefix is the key up
    to the first ``-``/``:``/``.`` delimiter — RESP arrays and inline
    commands both parse). All of the connection's traffic then rides
    that one group's log, so per-key linearizability holds as long as
    clients keep a connection's keys within one routing unit — the
    same client contract as Redis Cluster hash slots.
  * **CONNECT is held, not blocked.** The group is unknown until the
    first SEND names a key, so the CONNECT entry is held and acked
    immediately (it carries no data); when the first SEND pins group g
    the CONNECT is submitted ahead of it into g's log — FIFO within
    the group, so every replica replays CONNECT before the data, and
    an acked SEND transitively proves its CONNECT committed.
  * **Acks demux per group.** Commit waiters are tracked per
    ``(replica, group)`` FIFO; group g's commit stream releases only
    g's waiters, so groups committing at different rates can never
    reorder or cross-release acks.

**The client contract (with interposed apps).** Every writer of a key
reaches it through the SAME front-end: the app of the replica that
leads the key's group, on a connection whose keys all belong to that
group (a cluster-aware client holds one connection a group, as
``JedisCluster`` holds one a master). That app executes the request,
its reply is held until the group commits, and the other replicas' apps
get it replayed in that group's log order; keys of different groups
never meet, so the groups' streams into one app need no order between
them. A front-end handed a request for ANOTHER group's key does not
refuse it: ``_enqueue_locked`` pins the connection to the key's group
and the entry is forwarded to that group's leader's log. Its own app,
though, executed the request when it arrived, not where the log put
it: two front-ends executing writes to one key before the log orders
them can leave their apps different. No fencing stops that today, and
the honest alternative, a non-leader front-end refusing a write, is
left to an issue of its own (PERF.md sec. 7).

The pipelined dispatch loop (double-buffered ``begin_*``/``finish``,
readback thread) is inherited unchanged — the engines share one
ticket contract. Operator surfaces that are single-group by design
(membership change, snapshot recovery, app checkpoints, step-down
detection) are not supported in sharded mode and raise; ROADMAP item 4
(elastic resharding) is where they return.
"""

from __future__ import annotations

import collections
from typing import Dict, List, Optional

from rdma_paxos_tpu.config import LogConfig
from rdma_paxos_tpu.consensus.log import EntryType
from rdma_paxos_tpu.consensus.state import Role
from rdma_paxos_tpu.obs import trace as obs_trace
from rdma_paxos_tpu.obs.health import make_snapshot
from rdma_paxos_tpu.obs.tracectx import health_blame as _health_blame
from rdma_paxos_tpu.proxy.proxy import PendingEvent
from rdma_paxos_tpu.runtime.driver import ClusterDriver
from rdma_paxos_tpu.runtime.timers import GroupStepTimer
from rdma_paxos_tpu.shard.cluster import ShardedCluster
from rdma_paxos_tpu.shard.router import KeyRouter
from rdma_paxos_tpu.utils.codec import fragment

PREFIX_DELIMS = (b"-", b":", b".")


def key_prefix_of(payload: bytes) -> bytes:
    """The routing key prefix of a replicated SEND payload: the first
    command's key, truncated at the first prefix delimiter. Parses both
    RESP arrays (``*3\\r\\n$3\\r\\nSET\\r\\n$5\\r\\nkey-1...``) and
    inline/space-separated commands (``SET key-1 v1``). A payload with
    no recognizable key routes by the empty prefix (a legal router
    input) — deterministic, just unspread."""
    key = b""
    if payload[:1] == b"*":
        parts = payload.split(b"\r\n", 5)
        if len(parts) >= 5:
            key = parts[4]
    else:
        toks = payload.split(None, 2)
        if len(toks) >= 2:
            key = toks[1]
        elif toks:
            key = toks[0]
    # truncate at the FIRST-occurring delimiter (not the first in
    # PREFIX_DELIMS order): b"user.1-x" routes as b"user", never
    # b"user.1" — anything else would split a documented routing unit
    cut = len(key)
    for d in PREFIX_DELIMS:
        i = key.find(d, 0, cut)
        if i > 0:
            cut = i
    return key[:cut]


class ShardedClusterDriver(ClusterDriver):
    """One polling loop serving G consensus groups end to end."""

    def __init__(self, cfg: LogConfig, n_replicas: int, n_groups: int,
                 *, router: Optional[KeyRouter] = None,
                 key_of=key_prefix_of, mesh=None,
                 group_timer_lo: int = 6, group_timer_hi: int = 12,
                 **kw):
        if kw.get("link_model") is not None:
            raise ValueError(
                "sharded driver: attach per-group link models via "
                "cluster.link_models[g], not link_model=")
        self.G = int(n_groups)
        self._router = (router if router is not None
                        else KeyRouter(self.G))
        self._key_of = key_of
        # mesh=(group_shards, R) or a prebuilt 2-D Mesh routes the
        # engine onto the multi-chip (group, replica) layout; the
        # driver's pipelined loop is engine-agnostic (same ticket
        # contract), so nothing else changes
        self._mesh = mesh
        # per-group leader views (the sharded analog of _leader_view;
        # _leader_view itself becomes the ALL-GROUPS-LED aggregate so
        # leader()-polling boot code works unchanged)
        # guarded-by: _lock [writes]
        self._group_views: List[int] = [-1] * self.G
        # guarded-by: _lock
        self._conn_group: Dict[int, int] = {}    # conn -> pinned group
        # guarded-by: _lock
        self._conn_hold: Dict[int, tuple] = {}   # conn -> held CONNECT
        super().__init__(cfg, n_replicas, **kw)
        # (replica, group) commit-waiter FIFOs + replay cursors — the
        # single-group driver's rt.inflight / rt.replay_cursor, demuxed
        # guarded-by: _lock
        self._inflight_g: List[List[collections.deque]] = [
            [collections.deque() for _ in range(self.G)]
            for _ in range(n_replicas)]
        # guarded-by: _lock
        self._replay_cursor = [[0] * self.G for _ in range(n_replicas)]
        # per-group jittered STEP-DOMAIN election timers + candidate
        # rotation (group g's first candidate is replica g % R, so
        # converged leaderships land round-robin without any explicit
        # place_leaders choreography). Deterministic per-(seed, group)
        # periods: a chaos replay of the same step sequence redraws
        # identical timings — bit-reproducible, unlike wall clocks.
        seed = kw.get("seed", 0)
        self._gtimers = [GroupStepTimer(g, seed=seed,
                                        lo=group_timer_lo,
                                        hi=group_timer_hi)
                         for g in range(self.G)]
        self._elect_round = [0] * self.G
        # what the deployment adds to the single group's counters: read
        # from the first probe on, so they exist before any dispatch
        self.obs.metrics.inc("group_appends_total", 0)
        for g in range(self.G):
            self.obs.metrics.inc("group_acks_total", 0, group=g)
        # elastic-topology cutover hook: the controller calls this on
        # the driver thread right after the atomic router swap
        self.cluster._on_topology_cutover = self._on_topology_cutover

    def _make_cluster(self, cfg, n_replicas, group_size, mode, fanout,
                      audit, telemetry, txn=False):
        return ShardedCluster(cfg, n_replicas, self.G,
                              router=self._router, fanout=fanout,
                              group_size=group_size, audit=audit,
                              mesh=self._mesh, telemetry=telemetry,
                              scan=self._scan, txn=txn)

    def _wire_repair(self) -> None:
        """Sharded driver: repair uses the controller's ENGINE-level
        digest-verified install (per-group snapshot + backfill — one
        group's repair never stalls the others); the driver only
        resyncs its per-(replica, group) replay cursor. Store/app
        rebuild for a repaired front-end rides ROADMAP item 4
        (elastic resharding) — the repaired replica's consensus state
        and audit coverage are fully restored here."""
        self.repair.post_install = self._repair_post_install
        self.repair.on_quarantine = self._repair_on_quarantine

    def _repair_post_install(self, g: int, r: int, donor: int) -> None:
        with self._lock:
            self._replay_cursor[r][g] = len(self.cluster.replayed[g][r])

    def _repair_on_quarantine(self, g: int, r: int) -> None:
        """A front-end just entered quarantine for group ``g``: its
        replay/apply stream for that group is frozen, so its blocked
        commit waiters can never be ack-released — fail them now so
        clients retry against a healthy front-end (invoked by the
        controller OUTSIDE its lock)."""
        releases = []
        with self._lock:
            dq = self._inflight_g[r][g]
            n = len(dq)
            while dq:
                ev, _ = dq.popleft()
                releases.append(ev)
        for ev in releases:
            ev.release(-1)
        if releases:
            self.obs.metrics.inc("inflight_failed_total", len(releases),
                                 replica=r)
            self.obs.trace.record(obs_trace.INFLIGHT_FAILED,
                                  replica=r, group=g, count=len(releases),
                                  site="repair quarantine")
            self.obs.spans.fail_open(self._span_rep(g, r))

    def _span_rep(self, g: int, r: int) -> int:
        """Span-track replica id in the ENGINE's group namespace —
        delegated to the cluster so driver-side enqueue/ack/fail
        events land on the same per-group tracks as the engine's
        append/commit/apply stamps and the ``(group, term, index)``
        correlation closes end to end."""
        return self.cluster._span_rep(g, r)

    @property
    def router(self) -> KeyRouter:
        return self._router

    def leaders(self) -> List[int]:
        with self._lock:
            return list(self._group_views)

    # ------------------------------------------------------------------
    # intake: key-prefix routing (see module docstring)
    # ------------------------------------------------------------------

    def _accepts_clients(self, r: int) -> bool:
        # every replica fronts the cluster while any group is led (the
        # per-group availability check happens at SEND routing time) —
        # EXCEPT a replica the repair pipeline holds in any group: its
        # replay for the held group is frozen, so sessions it admits
        # could stall forever on ack release
        if (self.repair is not None
                and self.repair.serving_blocked_any(r)):
            return False
        return any(v >= 0 for v in self._group_views)

    def _enqueue_locked(self, r: int, rt, etype: int, conn_id: int,
                        payload: bytes):
        if etype == int(EntryType.CONNECT):
            # held until the first SEND names a key; acked immediately
            # (carries no data — an acked SEND later transitively
            # proves the CONNECT committed, FIFO within its group)
            self._conn_hold[conn_id] = payload
            self.obs.metrics.inc("proxy_events_total", replica=r)
            return 0
        g = self._conn_group.get(conn_id)
        if g is None and etype == int(EntryType.CLOSE):
            # nothing of this conn ever replicated
            self._conn_hold.pop(conn_id, None)
            return 0
        if g is None:
            g = self._router.group_of(self._key_of(payload))
            self._conn_group[conn_id] = g
        if self._group_views[g] < 0:
            # the routed group is (transiently) leaderless: fail fast
            # so the client retries — a commit wait could stall for a
            # whole election otherwise
            rt.replicated_conns.discard(conn_id)
            self._conn_group.pop(conn_id, None)
            self._conn_hold.pop(conn_id, None)
            self.obs.metrics.inc("events_refused_total", replica=r)
            return -1
        rows = []
        held = self._conn_hold.pop(conn_id, None)
        if held is not None:
            rt.submit_seq += 1
            rows.append((g, int(EntryType.CONNECT), conn_id, held,
                         rt.submit_seq))
        frags = (fragment(payload, self.cfg.slot_bytes)
                 if etype == int(EntryType.SEND) else [payload])
        ev = PendingEvent(EntryType(etype), conn_id, payload)
        for f in frags:
            rt.submit_seq += 1
            rows.append((g, etype, conn_id, f, rt.submit_seq))
        if etype == int(EntryType.CLOSE):
            self._conn_group.pop(conn_id, None)
        self._submitq[r].extend(rows)
        self._inflight_g[r][g].append((ev, rt.submit_seq))
        self._note_intake(ev, len(rows))     # a held CONNECT's too
        self.obs.metrics.inc("proxy_events_total", replica=r)
        self.obs.trace.record(obs_trace.PROXY_ENQUEUE, replica=r,
                              etype=etype, conn=conn_id, group=g,
                              frags=len(frags),
                              submit_seq=rt.submit_seq)
        # causal span birth keyed (conn, final fragment seq) — the
        # pair the per-group ack release matches on; the origin track
        # is the GROUP-NAMESPACED front-end replica, so the engine's
        # (group, term, index)-stamped append/commit/apply marks
        # correlate onto it
        self.obs.spans.begin(conn_id, rt.submit_seq,
                             self._span_rep(g, r))
        self._wake.set()
        return ev

    def _pump_submitq(self) -> None:
        with self._lock, self.cluster._host_lock:
            views = self._group_views
            for r in range(self.R):
                if not self._submitq[r]:
                    continue
                # demux the intake batch per group, then ONE locked
                # extend per (group, leader) — batched intake, no
                # per-entry Python. The group's CURRENT leader takes
                # the append; if leadership vanished since enqueue the
                # rows land on a non-leader and are dropped by design
                # — the leadership-change sweep fails their waiters
                per_g: Dict[int, list] = {}
                for g, etype, conn, frag, seq in self._submitq[r]:
                    per_g.setdefault(g, []).append(
                        (etype, conn, seq, frag))
                for g, rows in per_g.items():
                    q = views[g] if views[g] >= 0 else 0
                    self.cluster.submit_many(g, q, rows)
                self._submitq[r].clear()
            self._credit_intake()

    # ------------------------------------------------------------------
    # stepping
    # ------------------------------------------------------------------

    def _backlog(self) -> int:
        return max(len(q) for row in self.cluster.pending for q in row)

    # holds-lock: _lock
    def _waiter_count(self) -> int:
        return sum(len(dq) for row in self._inflight_g for dq in row)

    def _busy(self) -> bool:
        # checked OUTSIDE self._lock: the topology cutover hook runs
        # with the controller's lock held and takes self._lock
        # (topology._lock -> driver._lock); nesting the reverse order
        # here would deadlock
        topo = getattr(self.cluster, "topology", None)
        if topo is not None and (topo.needs_drain() or topo.cooling()):
            return True     # keep stepping so the window's records
            # land and the bounded post-window cooldown expires
        with self._lock:
            return bool(any(self._submitq) or self._backlog()
                        or self._waiter_count()
                        or (self.cluster.reads is not None
                            and self.cluster.reads.pending_count())
                        # in-flight transactions decide off the
                        # finish() tail — keep stepping until then
                        or (self.cluster.txn is not None
                            and self.cluster.txn.wants_serial()))

    def step(self) -> Dict:
        """One host-loop iteration: elections for leaderless groups
        ride the same dispatch as every other group's step; any
        backlog rides a fused all-groups burst."""
        self._phase_prof.start("admin_pump")
        self._drain_admin()
        self._pump_submitq()
        self._phase_prof.stop("admin_pump")
        c = self.cluster
        timeouts: Dict[int, list] = {}
        if c.last is not None:
            for g in range(self.G):
                if self._group_views[g] >= 0:
                    continue
                # leaderless groups tick their step-domain timer once
                # per poll iteration; a firing targets the rotation's
                # next candidate (start at g % R — the round-robin
                # spread place_leaders used to script explicitly).
                # Replicas the repair pipeline holds (quarantine /
                # probation) are skipped — a quarantined candidate is
                # cut from the hear-matrix and can never win anyway,
                # and a probation replica must not lead while its
                # clean-step hysteresis runs.
                if self._gtimers[g].tick():
                    cand = -1
                    for _ in range(self.R):
                        cc = (g + self._elect_round[g]) % self.R
                        self._elect_round[g] += 1
                        if not self._repair_blocked(cc, g):
                            cand = cc
                            break
                    if cand < 0:
                        continue        # every replica held — escalated
                    timeouts[g] = [cand]
                    self.obs.metrics.inc("election_timeouts_total",
                                         group=g)
        # governed tier: per-GROUP rung decisions share one dispatch,
        # so the program-level cap is the max rung (dec.max_k); a
        # serial decision routes through the all-groups single step
        dec = (self.governor.decision if self.governor is not None
               else None)
        if (not timeouts and c.last is not None
                and all(v >= 0 for v in self._group_views)
                and self._backlog()
                and not (c.txn is not None and c.txn.wants_serial())
                and (dec is None or dec.max_k > 1)):
            res = c.step_burst(max_k=dec.max_k if dec is not None
                               else None)
        else:
            res = c.step(timeouts=timeouts)
        return self._post_step(res)

    def _pipeline_ready(self) -> bool:
        c = self.cluster
        if c.last is None:
            return False
        if any(v < 0 for v in self._group_views):
            return False
        if c.need_recovery:
            return False
        # a due repair needs one drained serial iteration (per-group
        # surgery); depth-D pipelining re-engages right after
        if self.repair is not None and self.repair.needs_drain():
            return False
        if int(c.last["end"].max()) >= self.cfg.rebase_threshold:
            return False
        # an in-flight transaction holds the commit lane: votes and
        # decision records ride SERIAL dispatches (the same give-way
        # rule elections and repair follow)
        if c.txn is not None and c.txn.wants_serial():
            return False
        # an open topology transition window holds the serial path
        # (checked before self._lock — see _busy's lock-order note)
        topo = getattr(c, "topology", None)
        if topo is not None and topo.needs_drain():
            return False
        # the governor engages/disengages pipelining (see
        # ClusterDriver._pipeline_ready)
        if (self.governor is not None
                and not self.governor.decision.pipeline):
            return False
        # append batches only — see ClusterDriver._pipeline_ready
        with self._lock:
            return bool(any(self._submitq) or self._backlog())

    def _idle_margin(self) -> float:
        """The sharded election timers are STEP-DOMAIN (GroupStepTimer
        ticks once per poll iteration, and only for leaderless
        groups); the idle-skip gate already requires every group led
        (``_leader_view >= 0``), so no timer can fire while parked —
        the margin is unbounded and the backoff cap alone paces the
        heartbeat."""
        return float("inf")

    def _repair_held_any(self) -> bool:
        return any(self.repair.blocked_replicas(g)
                   for g in range(self.G))

    def _update_leader_view(self, res) -> None:
        views = []
        for g in range(self.G):
            # a repair-held replica's self-claim is not a serving
            # leadership: treating its group as leaderless fails the
            # waiters (clients retry) and lets the group timer elect a
            # healthy replacement instead of pinning the stale view
            claims = [(int(res["term"][g, r]), r)
                      for r in range(self.R)
                      if int(res["role"][g, r]) == int(Role.LEADER)
                      and not self._repair_blocked(r, g)]
            views.append(max(claims)[1] if claims else -1)
        with self._lock:
            prev = self._group_views
            self._group_views = views
            self._leader_view = (0 if all(v >= 0 for v in views)
                                 else -1)
        for g in range(self.G):
            if views[g] != prev[g] or views[g] < 0:
                # leadership moved or vanished: entries submitted to
                # the old leader may never commit — fail g's blocked
                # waiters so clients retry (late commits are harmless:
                # acks match by stamped seq, and released events are
                # terminal)
                self._fail_group_inflight(g, "leadership change")

    def _fail_group_inflight(self, g: int, site: str) -> None:
        with self._lock:
            for r in range(self.R):
                dq = self._inflight_g[r][g]
                n = len(dq)
                if not n:
                    continue
                rt = self.runtimes[r]
                if (rt.proxy is not None and rt.proxy.spec_mode
                        and not rt.app_dirty):
                    rt.app_dirty = True
                    rt.log.info_wtime(
                        "APP DIRTY: %d speculated events failed at %s "
                        "(group %d)" % (n, site, g))
                while dq:
                    ev, _ = dq.popleft()
                    ev.release(-1)
                self.obs.metrics.inc("inflight_failed_total", n,
                                     replica=r)
                self.obs.trace.record(obs_trace.INFLIGHT_FAILED,
                                      replica=r, group=g, count=n,
                                      site=site)
                # terminal failover status on the failed waiters'
                # spans (group-namespaced track) — never leaked
                self.obs.spans.fail_open(self._span_rep(g, r))

    def _on_topology_cutover(self, donors, targets) -> None:
        """An elastic cutover just swapped the live router: some keys
        moved OFF every group in ``donors``. Their blocked commit
        waiters are failed (clients retry and re-resolve the owner —
        same contract as a leadership change) and proxy conn->group
        pins on donor groups are dropped so the next SEND re-routes
        under the new map. Held CONNECTs stay held: they carry no key
        and route with their first SEND. Invoked by the topology
        controller (its lock held) on the driver thread — we take
        self._lock here, fixing the topology._lock -> driver._lock
        order the _busy/_pipeline_ready gates respect by checking
        ``needs_drain()`` OUTSIDE self._lock."""
        for g in donors:
            self._fail_group_inflight(g, "topology cutover")
        with self._lock:
            stale = [c for c, g in self._conn_group.items()
                     if g in donors]
            for c in stale:
                del self._conn_group[c]

    def _fail_inflight_locked(self, rt, site: str) -> None:
        """Fail EVERY group's blocked waiters on this replica (caller
        holds ``_lock``) — crash/stop paths."""
        n = sum(len(dq) for dq in self._inflight_g[rt.idx])
        if (n and rt.proxy is not None and rt.proxy.spec_mode
                and not rt.app_dirty):
            rt.app_dirty = True
            rt.log.info_wtime(
                "APP DIRTY: %d speculated events failed at %s"
                % (n, site))
        for g, dq in enumerate(self._inflight_g[rt.idx]):
            while dq:
                ev, _ = dq.popleft()
                ev.release(-1)
            self.obs.spans.fail_open(self._span_rep(g, rt.idx))
        if n:
            self.obs.metrics.inc("inflight_failed_total", n,
                                 replica=rt.idx)
            self.obs.trace.record(obs_trace.INFLIGHT_FAILED,
                                  replica=rt.idx, count=n, site=site)

    def _post_step(self, res) -> Dict:
        """The sharded loop's post-readback host rules, in the phases
        of ``ClusterDriver._post_step``: ``post_step_rules`` is all of
        it but the per-(replica, group) store/ack sweep, the replay to
        the apps and the observe pass, which are phases of their own."""
        prof = self._phase_prof
        prof.start("post_step_rules")
        self._update_leader_view(res)
        for g in range(self.G):
            if self._group_views[g] >= 0:
                self._gtimers[g].beat()
        prof.stop("post_step_rules")
        replays: list = []
        for r, rt in enumerate(self.runtimes):
            self._apply_new_entries(r, rt, replays)
        # every replica's app follows G - 1 groups: its operations of
        # all of them are one list, delivered in turns with the other
        # replicas' (log order within a group is what keeps the apps
        # equal; groups own disjoint keys)
        self._replay_in_turns(replays)
        prof.start("post_step_rules")
        # self-healing observation (same contract as the base driver's
        # _post_step): quarantine new findings / advance probation on
        # every finished step — the surgery itself waits for a drained
        # serial iteration (_drain_admin → repair.drive)
        if self.repair is not None:
            self.repair.observe()
        prof.stop("post_step_rules")
        prof.start("observe")
        self._observe_step(res)
        prof.stop("observe")
        return res

    # ------------------------------------------------------------------
    # apply / ack release (per group)
    # ------------------------------------------------------------------

    def _apply_new_entries(self, r: int, rt, replays: list) -> None:
        """Replica ``r``'s newly committed entries of every group, each
        through :meth:`ClusterDriver._apply_stream` (store, ack release
        of its own entries off that group's waiters); what its app is to
        be replayed, of all the groups it follows, is ONE entry of
        ``replays``."""
        c = self.cluster
        remote: list = []
        for g in range(self.G):
            stream = c.replayed[g][r]
            n = len(stream)
            cur = self._replay_cursor[r][g]
            if cur >= n:
                continue
            self._replay_cursor[r][g] = n
            with self._lock:
                waiters = self._inflight_g[r][g]
            acked = self._apply_stream(
                r, rt, stream, cur, c.frames[g], waiters,
                self._span_rep(g, r), remote)
            if acked:
                self.obs.metrics.inc("group_acks_total", acked, group=g)
        if remote:
            replays.append((rt.replay, remote))

    # ------------------------------------------------------------------
    # observability / health
    # ------------------------------------------------------------------

    def _observe_step(self, res) -> None:
        m = self.obs.metrics
        for r in range(self.R):
            m.set("inflight_waiters",
                  sum(len(dq) for dq in self._inflight_g[r]),
                  replica=r)
        m.set("cluster_leader", self._leader_view)
        self._cadence_observe()

    def _health_snapshots(self, res) -> Dict[int, Dict]:
        snaps = {}
        for r in range(self.R):
            rt = self.runtimes[r]
            snaps[r] = make_snapshot(
                replica=r,
                groups_led=[g for g in range(self.G)
                            if self._group_views[g] == r],
                inflight=sum(len(dq) for dq in self._inflight_g[r]),
                app_dirty=rt.app_dirty,
                store=(rt.store.stats() if rt.store is not None
                       else None))
        return snaps

    def health(self) -> Dict:
        """Sharded cluster health, conforming to the same
        ``obs.health.CLUSTER_HEALTH_FIELDS`` schema as the
        single-group driver's (``leaders`` stands in for
        ``leader``)."""
        from rdma_paxos_tpu.obs.health import make_cluster_snapshot
        h = self.cluster.health()
        h.pop("schema", None)     # the wrapper stamps the schema
        h.update(
            leaders=self.leaders(),
            all_groups_led=self.leader() >= 0,
            replicas=[snap for _, snap in
                      sorted(self._health_snapshots(None).items())],
            loop_error=(repr(self.loop_error) if self.loop_error
                        else None),
            alerts=self.alerts.state(),
            audit_artifact=self.audit_artifact,
            repair=(self.repair.status()
                    if self.repair is not None else None),
            reads=(self.cluster.reads.status()
                   if self.cluster.reads is not None else None),
            streams=(self.cluster.streams.status()
                     if self.cluster.streams is not None else None),
            governor=(self.governor.status()
                      if self.governor is not None else None),
            txn=(self.cluster.txn.health()
                 if self.cluster.txn is not None else None),
            blame=_health_blame(self.obs))
        return make_cluster_snapshot(**h)

    def read(self, fn=None, *, key=None, group: Optional[int] = None,
             replica: Optional[int] = None, timeout: float = 30.0):
        """Queue one linearizable read against the group owning
        ``key`` (or an explicit ``group``). The serving replica
        defaults to that group's lease holder — which
        ``place_leaders`` spreads across the R replicas, so read load
        fans out instead of piling onto one front-end. Same hub
        contract as the single-group driver: served on the readback
        thread between pipelined tickets, never through the log."""
        if group is None:
            if key is None:
                raise ValueError("read needs key= or group=")
            group = self._router.group_of(key)
        if replica is None:
            replica = self.read_replica(group)
        return super().read(fn, replica=replica, group=group,
                            timeout=timeout)

    def read_replica(self, group: int = 0) -> int:
        lm = self.cluster.leases
        r = lm.serving_holder(group) if lm is not None else -1
        if r < 0:
            with self._lock:
                r = self._group_views[group]
        return r if r >= 0 else 0

    def can_serve_read(self, r: int) -> bool:
        """True iff replica ``r`` verified its leadership on the latest
        step for EVERY group it leads (and leads at least one)."""
        last = self.cluster.last
        if last is None:
            return False
        led = [g for g in range(self.G) if self._group_views[g] == r]
        return bool(led) and all(
            bool(last["leadership_verified"][g, r]) for g in led)

    # ------------------------------------------------------------------
    # unsupported single-group operator surfaces
    # ------------------------------------------------------------------

    def request_membership(self, new_mask: int) -> None:
        raise NotImplementedError(
            "membership changes are single-group only (ROADMAP: "
            "elastic resharding)")

    def recover_replica(self, r, donor=None, timeout: float = 60.0,
                        wait_app: bool = True):
        raise NotImplementedError(
            "snapshot recovery is single-group only")

    def fail_replica(self, r: int) -> None:
        raise NotImplementedError(
            "a lost machine is modelled for a single group only")

    def reset_app(self, r: int, timeout: float = 60.0) -> None:
        raise NotImplementedError("app reset is single-group only")

    def checkpoint_app(self, r: int, timeout: float = 60.0) -> None:
        raise NotImplementedError(
            "app checkpoints are single-group only")
