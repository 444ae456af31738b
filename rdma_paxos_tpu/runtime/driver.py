"""ClusterDriver — the host polling loop gluing every layer together.

This is the analog of the reference's per-replica libev loop (``polling()``,
``dare_server.c:1004-1125``) plus the proxy callbacks, but driving ALL
replicas of an in-process cluster (the simulation/bring-up topology; the
multi-host deployment runs one driver per host over the same components):

  interposed app ──UDS──▶ ProxyServer ──queue──▶ ClusterDriver.step()
        ▲                                            │ SimCluster (jitted
        │ loopback TCP                               ▼  consensus step)
  ReplayEngine ◀──committed entries──┬── StableStore.append (persist)
                                     └── ack release (leader's blocked app)

Per iteration: drain shim events into leader batches → run the jitted
consensus step → persist newly applied entries → replay remote-origin
entries into local apps → release blocked app threads whose events
committed → run election timers (heartbeat = the step itself).
"""

from __future__ import annotations

import collections
import itertools
import os
import queue as _queue
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from rdma_paxos_tpu.config import LogConfig, TimeoutConfig
from rdma_paxos_tpu.consensus.log import EntryType
from rdma_paxos_tpu.consensus.membership import MembershipManager
from rdma_paxos_tpu.consensus.snapshot import (
    install_snapshot, recover_vote, take_snapshot)
from rdma_paxos_tpu.consensus.state import ConfigState, Role
from rdma_paxos_tpu.obs import Observability, trace as obs_trace
from rdma_paxos_tpu.obs.alerts import AlertEngine, default_rules
from rdma_paxos_tpu.obs.health import (
    HealthReporter, make_cluster_snapshot, make_snapshot)
from rdma_paxos_tpu.obs.metrics import (
    BATCH_BUCKETS, LATENCY_BUCKETS_S, LATENCY_BUCKETS_US)
from rdma_paxos_tpu.obs.spans import StepPhaseProfiler, span_trace_id
from rdma_paxos_tpu.obs.tracectx import health_blame as _health_blame
from rdma_paxos_tpu.proxy.proxy import (
    PendingEvent, ProxyServer, ReplayEngine, apply_record, dump_records,
    replay_store_into, spec_send_refused_dirty)
from rdma_paxos_tpu.proxy.stablestore import (
    HardState, StableStore, atomic_write)
from rdma_paxos_tpu.runtime.hostpath import plan_segment
from rdma_paxos_tpu.runtime.sim import SimCluster
from rdma_paxos_tpu.runtime.timers import ElectionTimer
from rdma_paxos_tpu.utils.debug import ReplicaLog
from rdma_paxos_tpu.utils.codec import fragment


def conn_origin(conn_id):
    """Origin replica/host encoded in a connection id (scalar
    or elementwise on numpy columns) — the ONE place the
    encoding lives."""
    return conn_id >> 24


class _ReplicaRuntime:
    """Host-side per-replica resources."""

    def __init__(self, idx: int, sock_path: Optional[str],
                 app_port: Optional[int], store_path: Optional[str],
                 on_event, timeout_cfg: TimeoutConfig, seed: int,
                 log_path: Optional[str] = None, obs=None):
        self.idx = idx
        self.log = ReplicaLog(log_path, replica=idx, obs=obs)
        self.app_port = app_port
        self.replay = (ReplayEngine("127.0.0.1", app_port)
                       if app_port else None)
        self.proxy = (ProxyServer(sock_path, idx, on_event, obs=obs,
                                  claim=self._claim)
                      if sock_path else None)
        # a SPECULATIVE app (shim HELLO flag) consumed input that was
        # failed at deposition — its state may have diverged from the
        # committed stream. While dirty: committed entries still persist
        # to the store (the store is the source of truth), but nothing
        # is replayed into the app and new client sessions are severed;
        # the operator restarts the app and calls reset_app().
        self.app_dirty = False
        # (store cursor, perf_counter at its start) of a fresh app's
        # background rebuild that has come within a few records of the
        # store's end and waits for the poll loop to finish it
        # (ClusterDriver._rebuild_app); ``app_dirty`` all the while
        self.rebuild: Optional[Tuple[int, float]] = None
        self.last_sync = 0.0      # cadenced store fdatasync bookkeeping
        self.store = StableStore(store_path) if store_path else None
        # durable (term, voted_term, voted_for) — persisted every step the
        # pair changes, restored by recover_replica (election safety
        # across crashes; rc_replicate_vote/rc_get_replicated_vote analog)
        self.hard = HardState(store_path + ".hs") if store_path else None
        # (event, last_fragment_seq) FIFO awaiting commit — every access
        # must hold the driver lock (link threads append, poll thread pops)
        self.inflight: collections.deque = collections.deque()
        self.submit_seq = 0       # monotone per-fragment sequence; stamped
                                  # into the entry's req_id so ack release
                                  # is exact across leadership churn
        self.replay_cursor = 0    # index into cluster.replayed[idx]
        self.replicated_conns: set = set()   # conns whose events replicate
        self.timer = ElectionTimer(timeout_cfg, seed=seed)
        # false-positive detection for the adaptive timeout (to_adjust_cb
        # analog): if the SAME leader heartbeats again shortly after we
        # fired, the timeout was premature -> widen it
        self.fired_leader = -1
        self.fired_countdown = 0

    def _claim(self, peer: bytes) -> bool:
        # the proxy server's question on a CONNECT, whatever engine
        # ``replay`` is by then (``reset_app`` replaces it)
        return self.replay is not None and self.replay.claim(peer)


class ClusterDriver:
    def __init__(self, cfg: LogConfig, n_replicas: int, *,
                 workdir: Optional[str] = None,
                 app_ports: Optional[Sequence[Optional[int]]] = None,
                 timeout_cfg: Optional[TimeoutConfig] = None,
                 group_size: Optional[int] = None,
                 mode: str = "sim", seed: int = 0,
                 auto_evict: bool = False, fail_threshold: int = 100,
                 sync_period: float = 0.05, step_down_steps: int = 50,
                 app_snapshot=None, fanout: str = "gather",
                 obs: Optional[Observability] = None,
                 health_period: float = 0.5, link_model=None,
                 fence: bool = False, audit: bool = False,
                 alert_rules: Optional[Sequence[dict]] = None,
                 alert_period: float = 0.25, pipeline: int = 2,
                 telemetry: bool = False,
                 profile_on_page: float = 0.0,
                 repair: bool = False,
                 repair_opts: Optional[Dict] = None,
                 leases: bool = True,
                 lease_opts: Optional[Dict] = None,
                 series_capacity: int = 1280,
                 metrics_port: Optional[int] = None,
                 scan: bool = False,
                 txn: bool = False,
                 governor: bool = False,
                 governor_opts: Optional[Dict] = None,
                 idle_quiesce: bool = True,
                 idle_backoff_max: float = 0.05,
                 streams: bool = False,
                 streams_opts: Optional[Dict] = None):
        self.cfg = cfg
        # scan=True engages the engine's device-resident K-window scan
        # tier on the burst path: one consolidated minimal readback
        # (scalars + in-dispatch replay rows) per K fused steps. The
        # flag lives on the cluster and is runtime-mutable
        # (driver.cluster.scan) — the host_path A/B flips it between
        # rounds; scan-off runs compile no scan programs.
        self._scan = bool(scan)
        # txn=True compiles the transaction vote-lane step variants
        # (txn/lane.py) so a coordinator can be attached
        # (txn.attach_coordinator over a ShardedKVS on this cluster);
        # txn=False programs and cache keys are bit-identical to the
        # unflagged world (tests/test_txn.py pins it)
        self._txn_flag = bool(txn)
        self.sync_period = sync_period
        self._workdir = workdir
        # observability: one registry + trace ring + span recorder per
        # driver (isolated by default — pass a shared facade to
        # aggregate across drivers). ALL instrumentation is host-side:
        # nothing below may run inside jitted code, and tests verify
        # compiled-step cache keys are unchanged by it.
        self.obs = obs if obs is not None else Observability()
        # step-phase wall-time attribution (obs.spans profiler). fence
        # keeps its default (False) in production: fencing blocks on
        # the step's outputs right after dispatch so device time lands
        # in its own device_sync histogram — a profiling mode that
        # serializes the dispatch pipeline, never the serving default.
        self._phase_prof = StepPhaseProfiler(
            metrics=self.obs.metrics, fence=fence, trace=self.obs.trace,
            step_index=lambda: self.cluster.step_index)
        self._health = (HealthReporter(workdir, period=health_period)
                        if workdir else None)
        # bounded recovery: optional app-level snapshot hook tuple
        # (dump_fn(sock)->bytes, restore_fn(sock, blob)[, probe_fn(sock)])
        # speaking the app's own protocol over a passthrough connection.
        # With it, checkpoint_app() captures a follower's app state at a
        # known store index and COMPACTS the store prefix it covers, so
        # donor transfer and fresh-app rebuild become O(app state +
        # suffix) instead of O(entire history) — exceeding the
        # reference, whose snapshot is always the full BDB record stream
        # (db-interface.c:98-134). probe_fn is the EXACT processed-input
        # barrier (request/response roundtrip on a replay connection,
        # returning once its own reply is observed); without it the
        # checkpoint falls back to kernel-queue quiescence, which can
        # still race an app that parks bytes in userspace buffers — see
        # ReplayEngine.quiesce. Supply probe_fn whenever the app's
        # protocol allows one.
        self.app_snapshot = app_snapshot
        # guarded-by: _lock [writes]
        self._ckpt_req: Optional[Tuple[int, threading.Event, list]] = None
        # lost-majority step-down (the reference leader SUICIDES after
        # failing to reach a majority, dare_server.c:1213-1217): a
        # leader whose leadership_verified stays 0 for this many
        # consecutive steps stops SERVING — inflight commits are failed
        # and replicated sessions severed/refused — so a minority-side
        # leader's clients retry against the majority instead of
        # hanging. Unlike the reference's process exit, service resumes
        # if the leader re-verifies (majority restored with no rival).
        self.step_down_steps = step_down_steps
        self.unverified = np.zeros(n_replicas, np.int64)
        self.stepped_down: set = set()
        self.R = n_replicas
        # fanout="psum" is the production full-connectivity
        # configuration (O(W) fan-out); the default stays "gather" so
        # tests can model partitions (see replica_step's docstring)
        # audit=True compiles the digest-chain step variants and runs
        # the cluster AuditLedger + flight recorder (obs/audit.py):
        # continuous proof that all R replicas hold bit-identical
        # committed state, with a bounded evidence ring dumped when
        # the digest-mismatch page fires
        # telemetry=True compiles the device-counter step variants
        # (obs/device.py): protocol counts as the DEVICE saw them,
        # ingested on the readback thread into device_* series — the
        # signals the telemetry-backed alert rules read
        self._telemetry = telemetry
        self.cluster = self._make_cluster(cfg, n_replicas, group_size,
                                          mode, fanout, audit, telemetry,
                                          self._txn_flag)
        self.cluster.obs = self.obs
        self.cluster.profiler = self._phase_prof
        # read scaling (runtime/reads.py): step-domain leader leases
        # renewed by the verified-quorum outputs every step already
        # carries, plus the queued read hub drained on the readback
        # thread between pipelined tickets. Host bookkeeping only —
        # reads never enter begin_*/finish, never consume ring slots,
        # never change a STEP_CACHE key.
        if leases:
            from rdma_paxos_tpu.runtime import reads as _reads
            _reads.attach(self.cluster, **(lease_opts or {}))
        # log-as-product streams (streams/): ordered range scans,
        # watch/subscribe with exactly-once resume, CDC export — one
        # tail-follower over the committed replay streams, observed at
        # the finish() tail. Host-side only: zero device changes, zero
        # new STEP_CACHE keys (tests/test_streams.py pins it). A
        # workdir defaults the CDC sink to <workdir>/cdc.jsonl when
        # streams_opts doesn't name one.
        self.streams = None
        if streams:
            from rdma_paxos_tpu import streams as _streams
            sopts = dict(streams_opts or {})
            if workdir and "cdc_path" not in sopts:
                sopts["cdc_path"] = os.path.join(workdir, "cdc.jsonl")
            if audit and "auditor" not in sopts:
                sopts["auditor"] = getattr(self.cluster, "auditor",
                                           None)
            self.streams = _streams.attach(self.cluster, obs=self.obs,
                                           **sopts)
        # time-series retention (obs/series.py): the registry sampled
        # into bounded per-series rings on the alert cadence — the
        # substrate the window-domain rules (rate_window / burn_rate)
        # and the /series endpoint read. With a workdir the samples
        # persist as append-only JSONL (cross-host merge = file
        # concat). Host bookkeeping only: no compiled program or
        # STEP_CACHE key changes (tests/test_ops_plane.py pins it).
        # Capacity must cover the LONGEST rule window at this cadence
        # (default 1280 x 0.25 s = 320 s > the 300 s slow burn
        # window) — a shorter ring saturates early and the slow
        # window degrades to full-retention, weakening the
        # multi-window transient suppression.
        from rdma_paxos_tpu.obs.series import TimeSeriesStore
        self.series = TimeSeriesStore(
            capacity=series_capacity,
            path=(os.path.join(workdir, "series.jsonl")
                  if workdir else None),
            source="driver")
        # SLO alert rules (obs/alerts.py) evaluated on a cadence from
        # the poll loop; firing state rides health snapshots and the
        # alert_firing{alert=...} gauges
        self.alerts = AlertEngine(
            self.obs.metrics,
            rules=(alert_rules if alert_rules is not None
                   else default_rules()),
            trace=self.obs.trace, series=self.series)
        self._alert_period = alert_period
        self._alert_last = float("-inf")
        self.exporter = None
        self._metrics_port = metrics_port
        self.audit_artifact: Optional[str] = None
        # self-healing (runtime/repair.py): repair=True closes the
        # audit loop — DIVERGENCE → quarantine → digest-verified
        # snapshot re-install from a ledger-majority donor →
        # range-digest backfill → probation re-admit. observe() runs
        # per finished step (readback thread); the state surgery runs
        # only on drained serial iterations (_drain_admin →
        # repair.drive; _pipeline_ready defers while a repair is due).
        self.repair = None
        if repair:
            if not audit:
                raise ValueError("repair=True requires audit=True "
                                 "(the ledger drives donor selection "
                                 "and install verification)")
            from rdma_paxos_tpu.runtime.repair import RepairController
            self.repair = RepairController(self.cluster, obs=self.obs,
                                           **(repair_opts or {}))
            self._wire_repair()
            self.alerts.add_hook(self.repair.on_alert)
        # adaptive dispatch governor (runtime/governor.py): a
        # step-domain feedback controller on the readback thread that
        # picks the dispatch tier (serial / burst K / scan K from the
        # prewarmed ladder), engages/disengages pipelining, and
        # applies a bounded admission-coalescing wait — and sheds to
        # serial the moment the commit-latency burn-rate pager fires
        # (AlertEngine.add_hook, the RepairController.on_alert
        # pattern), so it is a pure throughput win that can never
        # page the latency SLO. Host bookkeeping only: zero new
        # STEP_CACHE keys (tests/test_governor.py pins it).
        self.governor = None
        if governor:
            from rdma_paxos_tpu.runtime.governor import attach_governor
            self.governor = attach_governor(
                self.cluster, obs=self.obs, alerts=self.alerts,
                **(governor_opts or {}))
            self.alerts.add_hook(self.governor.on_alert)
        # idle quiescence: when there is no standing backlog, no
        # blocked waiter, no election timer anywhere near due, and no
        # admin/repair/config work, the poll loop SKIPS the device
        # dispatch entirely and parks with an exponential backoff —
        # instead of free-running heartbeat steps that burn the shared
        # core the app needs (the PR 8 idle-dispatch bias, closed at
        # the source). The alert/health cadences keep running while
        # parked, and any intake event wakes the loop instantly.
        self._idle_quiesce = bool(idle_quiesce)
        self._idle_backoff_max = float(idle_backoff_max)
        self._idle_backoff = 0.001
        self._idle_guard = (timeout_cfg.elec_timeout_low * 0.25
                            if timeout_cfg is not None else 0.025)
        # bounded jax.profiler captures (obs/device.py:ProfilerSession):
        # started via start_profile() (operator / bench CLI) or
        # automatically on the first page-severity alert when
        # profile_on_page > 0 (the capture duration in seconds); the
        # observe pass enforces the bound so an alert-triggered capture
        # can never run unbounded
        self.profile_session = None
        self._profile_on_page = float(profile_on_page)
        self._page_profiled = False
        # chaos hook: a per-link fault model (chaos.faults.LinkModel)
        # driven from outside the poll loop — fault-injection drills
        # against a LIVE driver (apps + stores + poll thread), not just
        # the bare sim. Host-side data rewrite only; with fanout="psum"
        # any non-full mask is rejected by the step, so chaos drills
        # require the default "gather".
        if link_model is not None:
            link_model.obs = self.obs
            self.cluster.link_model = link_model
        # absolute (rebase-corrected) commit cursor per replica, for the
        # committed_entries_total counters / commit_advance traces
        self._prev_commit_abs = np.zeros(n_replicas, np.int64)
        self.timeout_cfg = timeout_cfg or TimeoutConfig()
        # failure detection / eviction (check_failure_count analog):
        # consecutive steps each member failed to ack the leader's window
        self.auto_evict = auto_evict
        self.fail_threshold = fail_threshold
        self.fail_count = np.zeros(n_replicas, np.int64)
        # the failure detector takes the membership view from each
        # step's own ``res`` (the packed row carries it); the manager's
        # device-state reads serve the rare paths only, and only while
        # nothing is in flight (see _drive_config_change)
        self._mm = MembershipManager(self.cluster)
        # (phase, new_mask, epoch, steps_left) — steps_left bounds a change
        # wedged by leader churn losing the CONFIG entry; on expiry the
        # phase resets so eviction/request can be re-issued
        self._config_phase: Optional[Tuple[str, int, int, int]] = None
        self._config_t0 = 0.0     # perf_counter at its TRANSIT's submit
        self.config_changes_abandoned = 0
        # present at 0 from the start, so that a reader of deltas finds
        # them before the first change as after it
        for name in ("evictions_total", "config_changes_total",
                     "checkpoints_total"):
            self.obs.metrics.inc(name, 0)
        # replicas whose machine is lost (fail_replica) until a
        # replacement is recovered into their row
        self._lost: set = set()
        # (thread, replica, exception box) of each app rebuild started
        self._rebuilders: List[Tuple[threading.Thread, int, list]] = []
        # recovery requests execute inside the poll loop (never racing
        # the stepping thread over cluster.state): (replica, donor,
        # done_event, exception_box) — failures surface to the caller,
        # never kill the loop
        # guarded-by: _lock [writes]
        self._recover_req = None
        # app-reset requests (mis-speculation quarantine exit), same
        # poll-loop execution discipline: (replica, done_event, box)
        # guarded-by: _lock [writes]
        self._reset_req = None
        self._lock = threading.Lock()
        # per-replica queues of (etype, conn_id, fragment_bytes, seq)
        self._submitq: List[List[Tuple[int, int, bytes, int]]]
        self._submitq = [[] for _ in range(n_replicas)]  # guarded-by: _lock
        # operations queued since the last pump and the sum of their
        # intake stamps (PendingEvent.t0): the pump credits their queue
        # wait with one multiply, not one clock read an operation
        self._intake_n = 0          # guarded-by: _lock
        self._intake_t0_sum = 0.0   # guarded-by: _lock
        self._intake_frags = 0      # guarded-by: _lock
        self._intake_bytes = 0      # guarded-by: _lock
        # advisory leader view: written under the lock on the readback
        # thread; lock-free reads (poll/app threads) tolerate one step
        # of staleness by design  # guarded-by: _lock [writes]
        self._leader_view = -1
        # stores consume the vectorized frame stream from the decode
        self.cluster.collect_frames = workdir is not None
        self.runtimes: List[_ReplicaRuntime] = []
        for r in range(n_replicas):
            sock = (os.path.join(workdir, f"proxy{r}.sock")
                    if workdir else None)
            store = (os.path.join(workdir, f"replica{r}.db")
                     if workdir else None)
            port = app_ports[r] if app_ports else None
            logp = (os.path.join(workdir, f"replica{r}.log")
                    if workdir else None)
            self.runtimes.append(_ReplicaRuntime(
                r, sock, port, store,
                self._make_handler(r), self.timeout_cfg, seed + r,
                log_path=logp, obs=self.obs))
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self.loop_error: Optional[BaseException] = None
        # event-driven stepping: link threads set this when work arrives
        # so an idle loop wakes instantly instead of polling — on a
        # shared-core host a free-running loop would steal the CPU the
        # app itself needs (the reference's libev loop is fd-driven for
        # the same reason, dare_server.c:1004-1125)
        self._wake = threading.Event()
        # pipelined dispatch (the perf hot path): with pipeline >= 2 the
        # run loop keeps up to ``pipeline`` device dispatches in flight
        # — the dispatch thread encodes batch k+1 while batch k runs on
        # the device, and a dedicated READBACK thread blocks on outputs
        # and runs all post-step host work (requeue, replay, acks,
        # observability), so device_sync never serializes the enqueue
        # path. Election timeouts, admin requests (recover/reset/ckpt),
        # rebase drains, and recovery always drain the pipeline first
        # and run through the serial step() — pipelining is engaged
        # only on the stable-leader traffic path, where it is a pure
        # latency/throughput transform (the commit stream and ack
        # stream are bit-identical to the serial driver; tests pin it).
        # Mutable at runtime (A/B benches flip it between rounds).
        self.pipeline = max(int(pipeline), 0)
        self._pl_cv = threading.Condition()
        self._pl_pending = 0        # dispatched, not yet post-stepped
        self._pl_queue: _queue.Queue = _queue.Queue()
        self._rb_thread: Optional[threading.Thread] = None
        # opt-in ops exporter (obs/export.py): /metrics /healthz
        # /series /alerts on a localhost port (0 = ephemeral) — runs
        # beside the readback thread, never on the dispatch path.
        # Attached LAST: a scrape may land the instant the socket
        # binds, and health() touches everything above.
        if self._metrics_port is not None:
            self.serve_metrics(self._metrics_port)

    def _make_cluster(self, cfg, n_replicas, group_size, mode, fanout,
                      audit, telemetry, txn=False):
        """Engine factory (the sharded driver subclass overrides this
        to serve a multi-group ShardedCluster through the same loop)."""
        return SimCluster(cfg, n_replicas, group_size, mode=mode,
                          fanout=fanout, audit=audit,
                          telemetry=telemetry, scan=self._scan,
                          txn=txn)

    def _wire_repair(self) -> None:
        """Single-group driver: repair installs ride
        :meth:`_do_recover` (store transfer + live-app delta replay
        included) with the ledger passed through, so the install is
        digest-verified end to end and a corrupted donor raises into
        the controller's donor-retry loop."""
        self.repair.install_hook = self._repair_install

    def _repair_install(self, g: int, r: int, donor: int) -> None:
        self._do_recover(r, donor, app_fresh=False,
                         ledger=self.repair.led,
                         min_verified=self.repair.min_verified)
        # the device log + store are now healed from a digest-verified
        # donor, but a LIVE interposed app may already have executed
        # bytes the corruption reached before detection — its state
        # cannot be trusted either way (the audit cannot tell pre- from
        # post-replay corruption). Quarantine it through the existing
        # mis-speculation machinery: the store keeps persisting, and
        # the operator restarts the app + reset_app() rebuilds it from
        # the healed store. Consensus-level re-admission (leadership,
        # replication, audit coverage) completes automatically.
        rt = self.runtimes[r]
        if rt.replay is not None and not rt.app_dirty:
            rt.app_dirty = True
            rt.log.info_wtime(
                "REPAIR: app quarantined pending reset_app (its state "
                "may derive from corrupted committed bytes)")

    def _repair_blocked(self, r: int, group: int = 0) -> bool:
        return (self.repair is not None
                and self.repair.serving_blocked(group, r))

    # ------------------------------------------------------------------
    # shim event intake (called from proxy link threads)
    # ------------------------------------------------------------------

    def _make_handler(self, r: int):
        def on_event(etype: int, conn_id: int, payload: bytes):
            """Returns None (pass through), an int status (<0 severs the
            connection), or a PendingEvent (block until committed)."""
            with self._lock:
                rt = self.runtimes[r]

                def refuse_send():
                    """Refuse with -1, quarantining a speculative app
                    whose delivered bytes this refusal strands (shared
                    policy: proxy.spec_send_refused_dirty)."""
                    if spec_send_refused_dirty(
                            etype, conn_id, rt.replicated_conns,
                            rt.proxy, rt.app_dirty):
                        rt.app_dirty = True
                        rt.log.info_wtime(
                            "APP DIRTY: speculated SEND refused at "
                            "intake (conn %d)" % conn_id)
                    self.obs.metrics.inc("events_refused_total",
                                         replica=r)
                    return -1

                if self.loop_error is not None or self._stop.is_set():
                    # no poll loop will ever release a commit wait: fail
                    # fast so the app severs and the client retries
                    return refuse_send()
                if etype == int(EntryType.CONNECT):
                    # our own replay connections never come here (the
                    # proxy server answers them, _ReplicaRuntime._claim);
                    # client connections on non-leaders stay local
                    # (stale local reads — the reference's followers serve
                    # the same way, proxy.c:230-239 is_leader gate)
                    if rt.app_dirty:
                        # a dirty (mis-speculated) app must not serve
                        # clients — not even stale local reads
                        return -1
                    if not self._accepts_clients(r):
                        return None
                    if r in self.stepped_down:
                        # a stepped-down (majority-less) leader accepts
                        # no new sessions at all — the reference's
                        # suicided leader serves nothing
                        return -1
                    rt.replicated_conns.add(conn_id)
                    payload = b""
                elif conn_id not in rt.replicated_conns:
                    return None          # never-replicated local session
                elif r in self.stepped_down:
                    # lost-majority step-down: refuse replicated service
                    # (a commit wait could never complete)
                    status = refuse_send()
                    rt.replicated_conns.discard(conn_id)
                    return status
                elif rt.app_dirty:
                    # a surviving replicated session on a replica whose
                    # app diverged (mis-speculation) must be severed
                    # even if this replica regained leadership — its
                    # replies would come from state that does not match
                    # the committed stream
                    rt.replicated_conns.discard(conn_id)
                    return -1
                elif not self._accepts_clients(r):
                    # a REPLICATED session must never silently downgrade
                    # to unreplicated service after deposition: sever it
                    # so the client reconnects to the current leader
                    if etype == int(EntryType.CLOSE):
                        rt.replicated_conns.discard(conn_id)
                        return None
                    return refuse_send()
                if etype == int(EntryType.CLOSE):
                    rt.replicated_conns.discard(conn_id)
                return self._enqueue_locked(r, rt, etype, conn_id,
                                            payload)
        return on_event

    def _accepts_clients(self, r: int) -> bool:
        """Client-session admission: the single-group driver serves
        replicated sessions on the leader only (non-leaders give stale
        local reads, the reference's follower semantics) — and never a
        replica the repair pipeline holds in quarantine/probation. The
        sharded driver overrides this — every replica is a serving
        front-end demuxing onto the G group leaders."""
        return self._leader_view == r and not self._repair_blocked(r)

    def _enqueue_locked(self, r: int, rt: _ReplicaRuntime, etype: int,
                        conn_id: int, payload: bytes):
        """Admit one gate-passed replicated event: fragment, stamp
        sequence numbers, queue for the next dispatch, and park the
        blocked app thread's PendingEvent (caller holds ``_lock``).
        The sharded driver overrides this to pin the connection to its
        key-routed consensus group first."""
        frags = (fragment(payload, self.cfg.slot_bytes)
                 if etype == int(EntryType.SEND) else [payload])
        ev = PendingEvent(EntryType(etype), conn_id, payload)
        for f in frags:
            rt.submit_seq += 1
            self._submitq[r].append((etype, conn_id, f,
                                     rt.submit_seq))
        rt.inflight.append((ev, rt.submit_seq))
        self._note_intake(ev, len(frags))
        self.obs.metrics.inc("proxy_events_total", replica=r)
        self.obs.trace.record(obs_trace.PROXY_ENQUEUE,
                              replica=r, etype=etype,
                              conn=conn_id, frags=len(frags),
                              submit_seq=rt.submit_seq)
        # causal span birth: keyed (conn, final fragment seq) —
        # the exact pair the ack-release path matches on
        self.obs.spans.begin(conn_id, rt.submit_seq, r)
        self._wake.set()
        return ev

    # holds-lock: _lock
    def _note_intake(self, ev: PendingEvent, n_frags: int) -> None:
        """One operation admitted: its intake stamp, log entries and
        bytes join the sums the next pump credits."""
        self._intake_n += 1
        self._intake_t0_sum += ev.t0
        self._intake_frags += n_frags
        self._intake_bytes += len(ev.payload)

    # holds-lock: _lock
    def _credit_intake(self) -> None:
        """intake_queue_wait: intake to this pump, summed over the
        operations queued since the last one."""
        n = self._intake_n
        if n:
            wait = n * time.perf_counter() - self._intake_t0_sum
            self._phase_prof.credit("intake_queue_wait", wait * 1e6, n)
            self._intake_n, self._intake_t0_sum = 0, 0.0
            # log entries and bytes admitted since the last pump
            # (an operation over slot_bytes is several entries)
            self._phase_prof.count("intake_fragments_total",
                                   self._intake_frags)
            self._phase_prof.count("intake_payload_bytes_total",
                                   self._intake_bytes)
            self._intake_frags = self._intake_bytes = 0

    # ------------------------------------------------------------------
    # the polling loop
    # ------------------------------------------------------------------

    def _drain_admin(self) -> None:
        """Serve pending operator requests (recovery / app reset /
        checkpoint) — they execute on the stepping thread so they never
        race it over cluster state, and only with the dispatch pipeline
        fully drained."""
        # pop each request slot under the lock: the writers
        # (recover_replica / reset_app / checkpoint_app on caller
        # threads) publish under it, and an unlocked clear here could
        # lose a request armed between the read and the None-store
        # (graftlint lock-discipline rider)
        with self._lock:
            req, self._recover_req = self._recover_req, None
        if req is not None:
            r, donor, done, box = req
            try:
                self._do_recover(r, donor)
            except Exception as exc:  # noqa: BLE001 — reported to caller
                box.append(exc)
            finally:
                done.set()
        with self._lock:
            rreq, self._reset_req = self._reset_req, None
        if rreq is not None:
            r, done, box = rreq
            try:
                self._do_reset_app(r)
            except Exception as exc:  # noqa: BLE001 — reported to caller
                box.append(exc)
            finally:
                done.set()
        with self._lock:
            creq, self._ckpt_req = self._ckpt_req, None
        if creq is not None:
            r, done, box = creq
            try:
                self._do_checkpoint(r)
            except Exception as exc:  # noqa: BLE001 — reported to caller
                box.append(exc)
            finally:
                done.set()
        # self-healing: due repairs run HERE — the serial path, after
        # the dispatch loop drained every in-flight ticket (drive()
        # itself defers if anything is still in flight, the same
        # contract _drive_config_change uses)
        if self.repair is not None:
            self.repair.drive()
        # elastic topology: transition passes (seed/freeze/cutover)
        # run on the same drained serial path, after repair (repair
        # gets priority; the window defers or abandons around it)
        topo = getattr(self.cluster, "topology", None)
        if topo is not None:
            topo.drive()

    def _pump_submitq(self) -> None:
        """Move intake rows into the engine's pending queues — ONE
        locked extend per replica (batched intake, no per-entry
        Python). Holds the engine's host lock too: the pipelined
        readback thread requeues ring-full shortfalls into the same
        lists concurrently."""
        with self._lock, self.cluster._host_lock:
            for r in range(self.R):
                q = self._submitq[r]
                if q:
                    self.cluster.submit_many(
                        r, [(etype, conn, seq, frag)
                            for etype, conn, frag, seq in q])
                    q.clear()
            self._credit_intake()

    def step(self) -> Dict:
        """One host-loop iteration (public for deterministic tests).
        Serial: dispatch + readback fused — the pipelined run loop
        splits the same work into begin_* on the dispatch thread and
        ``_post_step`` on the readback thread."""
        self._phase_prof.start("admin_pump")
        self._drain_admin()
        self._pump_submitq()
        self._phase_prof.stop("admin_pump")

        # a flagged (force-pruned) leader never heals on its own: it
        # acks windows and heartbeats normally, so nothing deposes it,
        # its app/store stay frozen (stale reads), and every other
        # flagged member's recovery starves behind it. The same goes
        # for a leader the repair pipeline holds (quarantine cuts its
        # links, but it keeps self-claiming; probation must not lead
        # either). Actively depose it: fire an election timeout on a
        # healthy member each step until leadership moves
        # (run_until_elected cadence).
        depose = -1
        lead = self._leader_view
        if (lead >= 0
                and (lead in self.cluster.need_recovery
                     or self._repair_blocked(lead))):
            mask = self._mm.current(lead)["bitmask_new"]
            healthy = [r for r in range(self.R)
                       if (mask >> r) & 1 and r != lead
                       and r not in self.cluster.need_recovery
                       and not self._repair_blocked(r)]
            if healthy:
                depose = min(healthy)

        # pending work + known leader: drain through a multi-step burst
        # (one dispatch fuses up to K_TIERS[-1] protocol steps; no
        # election timeouts can fire inside — each burst step carries the
        # heartbeat, so follower timers are beaten right after). Bursts
        # are the DEFAULT e2e path — any backlog rides a fused dispatch;
        # the single-step path serves elections, deposes, and idle
        # heartbeats.
        # governed tier: the governor's decision caps the burst at a
        # lower ladder rung, or routes the iteration through the
        # serial single step entirely (latency-bound regime / SLO
        # shed). Ungoverned drivers keep the auto-sized burst.
        dec = (self.governor.decision if self.governor is not None
               else None)
        if (depose < 0
                and self._leader_view >= 0 and self.cluster.last is not None
                and self._backlog()
                and not (self.cluster.txn is not None
                         and self.cluster.txn.wants_serial())
                and (dec is None or dec.max_k > 1)):
            res = self.cluster.step_burst(
                max_k=dec.max_k if dec is not None else None)
        else:
            timeouts = []
            last = self.cluster.last
            for r, rt in enumerate(self.runtimes):
                if last is not None and last["role"][r] == int(Role.LEADER):
                    continue
                if rt.timer.expired() or r == depose:
                    timeouts.append(r)
                    rt.timer.beat()
                    self.obs.metrics.inc("election_timeouts_total",
                                         replica=r)
                    self.obs.trace.record(
                        obs_trace.ELECTION_START, replica=r,
                        depose=(r == depose),
                        term=(int(last["term"][r])
                              if last is not None else 0))
                    if r != depose:
                        # a deliberate deposition is not a mistimed
                        # timeout: it must not feed the adaptive
                        # false-positive widening (the flagged leader IS
                        # alive and heartbeating)
                        rt.fired_leader = (int(last["leader_id"][r])
                                           if last is not None else -1)
                        rt.fired_countdown = 50
            res = self.cluster.step(timeouts=timeouts)
        return self._post_step(res)

    def _backlog(self) -> int:
        """Entries awaiting dispatch in the engine's pending queues."""
        return max(len(q) for q in self.cluster.pending)

    def _update_leader_view(self, res) -> None:
        with self._lock:
            # multiple self-claimed leaders can coexist transiently (an
            # isolated deposed leader cannot hear the higher term); the
            # real one is the highest-term claimant — terms are unique per
            # leader by quorum election
            claims = [(int(res["term"][r]), r) for r in range(self.R)
                      if res["role"][r] == int(Role.LEADER)]
            self._leader_view = max(claims)[1] if claims else -1

    def _post_step(self, res) -> Dict:
        """Every post-readback host rule for one step's outputs: leader
        view, durable election state, timer beats, store/replay/ack
        release, detectors, recovery drive, and observability export.
        Serial ``step()`` runs it inline; the pipelined loop runs it on
        the READBACK thread, so none of this work — observability
        included — can serialize the dispatch path it measures.
        ``post_step_rules`` is everything here but the replay/ack sweep
        and the observe pass, which are phases of their own: it stops
        round each ``_apply_new_entries``."""
        prof = self._phase_prof
        prof.start("post_step_rules")
        self._update_leader_view(res)

        replays: list = []
        for r, rt in enumerate(self.runtimes):
            if rt.hard is not None:
                rt.hard.save(int(res["term"][r]),
                             int(res["voted_term"][r]),
                             int(res["voted_for"][r]))
            if res["became_leader"][r]:
                rt.log.leader_elected(int(res["term"][r]))
            if res["hb_seen"][r] or res["role"][r] == int(Role.LEADER):
                rt.timer.beat()
            if rt.fired_countdown > 0:
                rt.fired_countdown -= 1
                if (res["hb_seen"][r] and rt.fired_leader >= 0
                        and int(res["leader_id"][r]) == rt.fired_leader):
                    # the leader we timed out on is alive: premature
                    # timeout -> widen adaptively (to_adjust_cb analog)
                    rt.timer.false_positive()
                    rt.fired_countdown = 0
            prof.stop("post_step_rules")
            self._apply_new_entries(r, rt, replays)
            prof.start("post_step_rules")
            if res["role"][r] != int(Role.LEADER):
                with self._lock:
                    # lost leadership with blocked app threads: fail them
                    # so clients reconnect to the new leader (reference
                    # clients time out the same way). Fragments already
                    # replicated may still commit later; seq-stamped acks
                    # make those late applies harmless no-ops.
                    self._fail_inflight_locked(rt, "deposition")
        prof.stop("post_step_rules")
        self._replay_in_turns(replays)
        prof.start("post_step_rules")

        self._step_down_detector(res)
        self._failure_detector(res)
        self._drive_config_change()
        # self-healing observation: consume new DIVERGENCE findings
        # (quarantine is host bookkeeping — safe on this, the readback,
        # thread) and advance probation hysteresis; the state surgery
        # itself waits for a drained serial iteration (_drain_admin)
        if self.repair is not None:
            self.repair.observe()
        # a replica force-pruned past its apply cursor (wedged app now
        # unwedged, or long stall) stopped replaying; heal it with a
        # donor snapshot — the reference's straggler-eviction-then-
        # rejoin collapsed into one step (one per iteration). Replicas
        # the repair controller owns are ITS to heal (ledger-verified
        # donor), not this default path's.
        if (self.cluster.need_recovery
                and self._leader_view >= 0
                # never under in-flight dispatches: snapshot install
                # rewrites cluster state the pipeline is still feeding
                # (the dispatch loop sees need_recovery and drains, so
                # the next drained iteration takes this branch)
                and not self.cluster._tickets
                # the donor is the leader: it must itself be healthy —
                # a flagged leader's host store is frozen, so its
                # snapshot would silently drop acked writes; wait for
                # leadership to move to a usable member instead
                and self._leader_view not in self.cluster.need_recovery):
            # never pick the leader itself as the recoveree either (a
            # flagged replica can still win elections — it acks windows
            # regardless of apply); it recovers once deposed, and must
            # not starve the others
            owned = (self.repair.owned() if self.repair is not None
                     else set())
            cands = (self.cluster.need_recovery - {self._leader_view}
                     - owned)
            if cands:
                r = min(cands)
                try:
                    self._do_recover(r, None, app_fresh=False)
                except RuntimeError as exc:
                    # unrecoverable in place (e.g. the donor compacted
                    # past this app's applied prefix): quarantine the
                    # app for an operator restart + reset_app rather
                    # than killing the poll loop or retrying forever
                    rt = self.runtimes[r]
                    rt.app_dirty = True
                    rt.log.info_wtime("AUTO-RECOVERY FAILED: %s" % exc)
                self.cluster.need_recovery.discard(r)
        prof.stop("post_step_rules")
        prof.start("observe")
        self._observe_step(res)
        prof.stop("observe")
        return res

    # ------------------------------------------------------------------
    # observability (host-side only — see rdma_paxos_tpu.obs)
    # ------------------------------------------------------------------

    def _observe_step(self, res) -> None:
        """Export the step's protocol-level signals: per-replica
        role/term/index gauges, rebase headroom against the i32
        ceiling, commit-advance counters + trace, batch-size histogram,
        and the cadenced health snapshot files."""
        m = self.obs.metrics
        rebased = getattr(self.cluster, "rebased_total", 0)
        for r in range(self.R):
            m.set("replica_role", int(res["role"][r]), replica=r)
            m.set("replica_term", int(res["term"][r]), replica=r)
            m.set("commit_index", int(res["commit"][r]), replica=r)
            m.set("apply_index", int(res["apply"][r]), replica=r)
            m.set("end_index", int(res["end"][r]), replica=r)
            m.set("rebase_headroom",
                  self.cfg.rebase_threshold - int(res["end"][r]),
                  replica=r)
            m.set("inflight_waiters", len(self.runtimes[r].inflight),
                  replica=r)
            acc = int(res["accepted"][r])
            if acc > 0:
                m.inc("accepted_entries_total", acc, replica=r)
                m.observe("step_batch_entries", acc,
                          buckets=BATCH_BUCKETS, replica=r)
                self.obs.trace.record(obs_trace.STEP_BATCH, replica=r,
                                      entries=acc)
            commit_abs = int(res["commit"][r]) + rebased
            delta = commit_abs - int(self._prev_commit_abs[r])
            if delta > 0:
                self._prev_commit_abs[r] = commit_abs
                m.inc("committed_entries_total", delta, replica=r)
                self.obs.trace.record(obs_trace.COMMIT_ADVANCE,
                                      replica=r, commit=commit_abs,
                                      delta=delta)
        # cluster-level leader view (the leaderless alert's input)
        m.set("cluster_leader", self._leader_view)
        self._cadence_observe()

    def _cadence_observe(self) -> None:
        """The wall-cadenced observability work (alert evaluation +
        series sampling, profiler expiry, health snapshot files) —
        shared by the per-step observe pass AND the idle-quiescence
        branch, so a parked poll loop keeps its alerts and health
        files fresh while skipping device dispatches."""
        now = time.monotonic()
        if now - self._alert_last >= self._alert_period:
            self._alert_last = now
            self.evaluate_alerts()
        self._poll_profile()
        if self._health is not None and self._health.due():
            try:
                # ONE health() pass feeds both files: the per-replica
                # snapshots and the cluster-level document (leader
                # view, lease/read status, repair state, ALERT firing
                # state — the file-based console's and the postmortem
                # bundle's cluster source)
                h = self.health()
                self._health.write({rep["replica"]: rep
                                    for rep in h["replicas"]})
                self._health.write_cluster(h)
            except OSError:
                # observability I/O must never kill the data path: a
                # vanished workdir / full disk costs the snapshot, not
                # the poll loop (an OSError here would otherwise be
                # treated as a fatal step crash and fail every inflight
                # commit)
                pass

    def _health_snapshots(self, res) -> Dict[int, Dict]:
        """Per-replica health dicts (the obs.health schema plus store /
        rebase extras) — written to ``replica<r>.health.json`` on the
        reporter cadence and aggregated live by :meth:`health`."""
        snaps = {}
        for r in range(self.R):
            rt = self.runtimes[r]
            snaps[r] = make_snapshot(
                replica=r,
                role=int(res["role"][r]),
                term=int(res["term"][r]),
                leader_id=int(res["leader_id"][r]),
                commit=int(res["commit"][r]),
                apply=int(res["apply"][r]),
                end=int(res["end"][r]),
                head=int(res["head"][r]),
                log_headroom=(self.cfg.rebase_threshold
                              - int(res["end"][r])),
                inflight=len(rt.inflight),
                app_dirty=rt.app_dirty,
                stepped_down=r in self.stepped_down,
                need_recovery=r in self.cluster.need_recovery,
                rebases=self.cluster.rebases,
                rebase_stalled=self.cluster.rebase_stalled,
                store=(rt.store.stats() if rt.store is not None
                       else None),
            )
        return snaps

    def evaluate_alerts(self) -> Dict:
        """One SLO-rule evaluation pass (also called on a cadence from
        the poll loop). A newly-firing ``page``-severity alert on an
        audited cluster dumps the audit artifact (ledger + flight ring
        + obs dumps) for post-mortem, and — with ``profile_on_page``
        set — starts ONE bounded device-profiler capture so the pages'
        root cause is inspectable on the device timeline.

        The series store samples FIRST, from the same registry
        snapshot the rules then evaluate — so the window-domain rules
        (rate_window / burn_rate) always see the freshest point and
        the retention cadence IS the alert cadence."""
        snap = self.obs.metrics.snapshot()
        if self.series is not None:
            self.series.sample(snap,
                               step=int(self.cluster.step_index))
        out = self.alerts.evaluate(snap=snap)
        pages = [n for n in out["fired"]
                 if self.alerts.severity(n) == "page"]
        if pages and (self.cluster.auditor is not None
                      or self.cluster.flight is not None):
            self._dump_audit_artifact("alert: " + ",".join(pages))
        if (pages and self._profile_on_page > 0
                and not self._page_profiled):
            self._page_profiled = True      # one capture per process
            try:
                self.start_profile(seconds=self._profile_on_page)
                self.obs.trace.record(obs_trace.ALERT_FIRED,
                                      alert="profile_capture",
                                      severity="info",
                                      value=",".join(pages))
            except RuntimeError:
                pass        # another capture is active — keep serving
        return out

    # ------------------------------------------------------------------
    # bounded device-profiler captures (obs/device.py:ProfilerSession)
    # ------------------------------------------------------------------

    def start_profile(self, seconds: float = 5.0,
                      log_dir: Optional[str] = None):
        """Begin a bounded ``jax.profiler`` capture of the serving
        path; the poll loop stops it when ``seconds`` elapse (or call
        :meth:`stop_profile`). The capture's Chrome trace merges onto
        the span timeline via ``obs.device.merge_timeline``."""
        from rdma_paxos_tpu.obs.device import ProfilerSession
        if self.profile_session is not None \
                and self.profile_session.active:
            raise RuntimeError("a profiler capture is already active")
        if log_dir is None:
            import tempfile
            log_dir = (os.path.join(self._workdir, "profile")
                       if self._workdir else
                       tempfile.mkdtemp(prefix="rp_profile_"))
        self.profile_session = ProfilerSession(
            log_dir, max_seconds=seconds).start()
        return self.profile_session

    def stop_profile(self):
        """Stop the active capture (idempotent); returns the session
        (trace files resolved) or None when none was started."""
        if self.profile_session is not None:
            self.profile_session.stop()
        return self.profile_session

    def _poll_profile(self) -> None:
        """Observe-pass hook: expire a bounded capture. Profiler I/O
        must never kill the data path."""
        s = self.profile_session
        if s is not None and s.active:
            try:
                s.maybe_stop()
            except Exception:  # noqa: BLE001 — evidence, not data path
                pass    # stop() already marked the session inactive

    def _dump_audit_artifact(self, reason: str) -> Optional[str]:
        from rdma_paxos_tpu.obs.audit import write_audit_artifact
        path = (os.path.join(self._workdir, "audit_dump.json")
                if self._workdir else None)
        try:
            self.audit_artifact = write_audit_artifact(
                path, reason=reason, ledger=self.cluster.auditor,
                flight=self.cluster.flight, obs=self.obs,
                config=dict(n_replicas=self.R,
                            n_slots=self.cfg.n_slots,
                            slot_bytes=self.cfg.slot_bytes,
                            window_slots=self.cfg.window_slots))
        except OSError:
            # evidence I/O must never kill the data path
            return None
        self.obs.trace.record(obs_trace.AUDIT_DUMPED, reason=reason,
                              path=self.audit_artifact)
        return self.audit_artifact

    def health(self) -> Dict:
        """Aggregated cluster health (live — not from the files): the
        per-replica snapshots plus the cluster-level view, conforming
        to ``obs.health.CLUSTER_HEALTH_FIELDS`` (validate with
        ``obs.health.validate_cluster``). Safe to call from any
        thread; uses the last completed step's outputs."""
        res = self.cluster.last
        replicas = (self._health_snapshots(res) if res is not None
                    else {})
        return make_cluster_snapshot(
            leader=self.leader(),
            n_replicas=self.R,
            replicas=[replicas[r] for r in sorted(replicas)],
            rebases=self.cluster.rebases,
            rebase_stalled=self.cluster.rebase_stalled,
            loop_error=(repr(self.loop_error)
                        if self.loop_error else None),
            audit=(self.cluster.auditor.summary()
                   if self.cluster.auditor is not None else None),
            alerts=self.alerts.state(),
            audit_artifact=self.audit_artifact,
            repair=(self.repair.status()
                    if self.repair is not None else None),
            leases=(self.cluster.leases.status()
                    if self.cluster.leases is not None else None),
            reads=(self.cluster.reads.status()
                   if self.cluster.reads is not None else None),
            streams=(self.cluster.streams.status()
                     if self.cluster.streams is not None else None),
            governor=(self.governor.status()
                      if self.governor is not None else None),
            txn=(self.cluster.txn.health()
                 if self.cluster.txn is not None else None),
            blame=_health_blame(self.obs),
        )

    # ------------------------------------------------------------------
    # the ops exporter (obs/export.py) — /metrics /healthz /series
    # /alerts beside the readback thread, never on the dispatch path
    # ------------------------------------------------------------------

    def serve_metrics(self, port: int = 0):
        """Start (or return) the opt-in localhost ops exporter:
        ``/metrics`` (Prometheus text), ``/metrics.json``,
        ``/healthz`` (503 on a dead poll loop), ``/series``,
        ``/alerts``. ``port=0`` binds an ephemeral port — read it
        back from ``driver.exporter.port``. Pure host-side serving of
        already-thread-safe read surfaces; programs and STEP_CACHE
        keys are untouched (pinned by test)."""
        if self.exporter is None:
            from rdma_paxos_tpu.obs.export import OpsExporter
            self.exporter = OpsExporter(
                registry=self.obs.metrics, health_fn=self.health,
                alerts=self.alerts, series=self.series,
                port=port).start()
        return self.exporter

    # ------------------------------------------------------------------
    # failure detection + eviction (push-detection analog: WC failures
    # -> fail_count >= threshold -> CONFIG removal, dare_server.c:1189)
    # ------------------------------------------------------------------

    def _fail_inflight_locked(self, rt: _ReplicaRuntime,
                              site: str) -> None:
        """Fail every blocked commit waiter (caller holds the lock). A
        SPECULATIVE app already executed the inputs being failed, so its
        state may have diverged from the committed stream — quarantine
        it (app_dirty) until rebuilt via reset_app."""
        if (rt.inflight and rt.proxy is not None
                and rt.proxy.spec_mode and not rt.app_dirty):
            rt.app_dirty = True
            rt.log.info_wtime(
                "APP DIRTY: %d speculated events failed at %s"
                % (len(rt.inflight), site))
        n = len(rt.inflight)
        while rt.inflight:
            ev, _ = rt.inflight.popleft()
            ev.release(-1)
        if n:
            self.obs.metrics.inc("inflight_failed_total", n,
                                 replica=rt.idx)
            self.obs.trace.record(obs_trace.INFLIGHT_FAILED,
                                  replica=rt.idx, count=n, site=site)
            # close the failed waiters' spans with a terminal failover
            # status — orphaned spans must never leak across leadership
            # churn (nothing will ever ack them)
            self.obs.spans.fail_open(rt.idx)

    def _step_down_detector(self, res) -> None:
        """Lost-majority step-down (dare_server.c:1213-1217 analog): a
        leader that cannot verify its authority against a majority for
        ``step_down_steps`` consecutive steps stops serving — blocked
        commit waiters fail (clients retry elsewhere) and replicated
        sessions are refused until it re-verifies or is deposed."""
        for r in range(self.R):
            is_lead = res["role"][r] == int(Role.LEADER)
            if is_lead and not res["leadership_verified"][r]:
                self.unverified[r] += 1
            else:
                self.unverified[r] = 0
                if r in self.stepped_down:
                    self.stepped_down.discard(r)
                    self.runtimes[r].log.info_wtime(
                        "REJOINED: leadership re-verified or deposed")
            if (is_lead and r not in self.stepped_down
                    and self.unverified[r] >= self.step_down_steps):
                self.stepped_down.add(r)
                rt = self.runtimes[r]
                # a majority-less leader must not serve lease reads
                # either: revoke before the serving gates react
                if self.cluster.leases is not None:
                    self.cluster.leases.revoke_all(r, "step_down")
                self.obs.metrics.inc("step_downs_total", replica=r)
                self.obs.trace.record(obs_trace.STEP_DOWN, replica=r,
                                      term=int(res["term"][r]),
                                      unverified=int(self.unverified[r]))
                rt.log.info_wtime(
                    "[T%d] LOST MAJORITY: stepping down after %d "
                    "unverified steps" % (int(res["term"][r]),
                                          int(self.unverified[r])))
                # replicated_conns is deliberately NOT cleared: removing
                # a session from the set would downgrade its next event
                # to unreplicated pass-through (acked lost write); the
                # stepped_down branch in on_event severs each surviving
                # session on its next event instead.
                with self._lock:
                    self._fail_inflight_locked(rt, "step-down")

    def _failure_detector(self, res) -> None:
        """Count the steps each member failed to ack the leader's
        window, against the leader's config view of the SAME step
        (both ride the packed readback row: no device read here)."""
        lead = self._leader_view
        if lead < 0:
            self.fail_count[:] = 0
            return
        mask = int(res["bitmask_new"][lead])
        epoch = int(res["epoch"][lead])
        acked = res["peer_acked"][lead]
        for r in range(self.R):
            if not (mask >> r) & 1 or r == lead:
                self.fail_count[r] = 0
                continue
            self.fail_count[r] = 0 if acked[r] else self.fail_count[r] + 1
        if not self.auto_evict or self._config_phase is not None:
            return
        dead = [r for r in range(self.R)
                if (mask >> r) & 1 and self.fail_count[r]
                >= self.fail_threshold]
        if dead:
            new_mask = mask
            for r in dead:
                new_mask &= ~(1 << r)
            # only evict a strict MINORITY: the survivors must form a
            # majority of the current group, else a transient partition
            # of live nodes would permanently shrink fault tolerance
            survivors = bin(new_mask).count("1")
            if survivors > bin(mask).count("1") // 2:
                self._mm.submit_transit(lead, mask, new_mask, epoch + 1)
                self._config_phase = ("transit", new_mask, epoch + 1, 500)
                self._config_t0 = time.perf_counter()
                self.obs.metrics.inc("evictions_total", len(dead))
                self.obs.trace.record(obs_trace.MEMBERSHIP_CHANGE,
                                      phase="evict_transit", dead=dead,
                                      new_mask=new_mask, epoch=epoch + 1)

    def _drive_config_change(self) -> None:
        """Advance a two-phase (joint-consensus) config change one poll
        iteration at a time — the non-blocking version of
        MembershipManager.change for use inside the polling loop."""
        if self._config_phase is None:
            return
        # under pipelining this runs on the readback thread: in-flight
        # dispatches may have donated the device buffers _mm.current
        # reads, and a concurrent batch take would race submit_stable.
        # The engine host lock brackets every dispatch, so holding it
        # with tickets empty proves no donation can land mid-read —
        # and _pipeline_ready sees the phase and drains, so a deferred
        # iteration drives the change serially (TTL untouched).
        with self.cluster._host_lock:
            if self.cluster._tickets:
                return
            phase, new_mask, epoch, ttl = self._config_phase
            if ttl <= 0:
                # CONFIG entry lost (e.g. leader deposed before it
                # replicated): abandon so the failure detector /
                # operator can resubmit
                self._config_phase = None
                self.config_changes_abandoned += 1
                self.obs.metrics.inc("config_changes_abandoned_total")
                self.obs.trace.record(obs_trace.MEMBERSHIP_CHANGE,
                                      phase="abandoned",
                                      new_mask=new_mask, epoch=epoch)
                return
            self._config_phase = (phase, new_mask, epoch, ttl - 1)
            lead = self._leader_view
            if lead < 0:
                return
            cur = self._mm.current(lead)
            last = self.cluster.last
            committed = (last is not None and
                         int(last["commit"][lead])
                         >= int(last["end"][lead]))
            if phase == "transit":
                if (cur["epoch"] >= epoch
                        and cur["cid_state"] == int(ConfigState.TRANSIT)
                        and committed):
                    self._mm.submit_stable(lead, new_mask, epoch + 1)
                    self._config_phase = ("stable", new_mask,
                                          epoch + 1, ttl)
                    self.obs.trace.record(obs_trace.MEMBERSHIP_CHANGE,
                                          phase="stable_submitted",
                                          new_mask=new_mask,
                                          epoch=epoch + 1)
            elif phase == "stable":
                if (cur["epoch"] >= epoch
                        and cur["cid_state"] == int(ConfigState.STABLE)):
                    self._config_phase = None
                    self._phase_prof.credit(
                        "config_change",
                        (time.perf_counter() - self._config_t0) * 1e6)
                    self.obs.metrics.inc("config_changes_total")
                    self.obs.trace.record(obs_trace.MEMBERSHIP_CHANGE,
                                          phase="complete",
                                          new_mask=new_mask, epoch=epoch)

    def request_membership(self, new_mask: int) -> None:
        """Operator API: start a two-phase change to ``new_mask`` (join /
        upsize / downsize); the polling loop drives it to completion."""
        lead, last = self._leader_view, self.cluster.last
        if lead < 0 or last is None:
            raise RuntimeError("no leader")
        if self._config_phase is not None:
            raise RuntimeError("a membership change is being driven")
        # the leader's view as the last finished step's packed row has
        # it (with no change in flight it is the current one): the
        # device state itself may be donated to a dispatch in flight,
        # and this is an operator's thread
        epoch = int(last["epoch"][lead]) + 1
        self._mm.submit_transit(lead, int(last["bitmask_new"][lead]),
                                new_mask, epoch)
        self._config_phase = ("transit", new_mask, epoch, 500)
        self._config_t0 = time.perf_counter()
        self.obs.trace.record(obs_trace.MEMBERSHIP_CHANGE,
                              phase="transit_requested",
                              new_mask=new_mask, epoch=epoch)

    def membership(self) -> Optional[Dict]:
        """The leader's configuration as of the last finished step
        (its packed row: no device read): ``mask`` of the members,
        ``stable`` (no joint phase), ``epoch``, and ``changing`` while
        the loop still drives a change. None without a leader."""
        lead, last = self._leader_view, self.cluster.last
        if lead < 0 or last is None:
            return None
        return dict(mask=int(last["bitmask_new"][lead]),
                    stable=(int(last["cid_state"][lead])
                            == int(ConfigState.STABLE)),
                    epoch=int(last["epoch"][lead]),
                    changing=self._config_phase is not None)

    def fail_replica(self, r: int) -> None:
        """Replica ``r``'s machine is lost, as far as one process can
        render it: from the next dispatch on its row hears nobody and
        nobody hears it (``cluster.partition``), and its election timer
        stops, as a dead machine's does (left running, its candidacies
        would raise a term that deposes the leader the moment the row
        is heard again). The group is told nothing: the leader's
        failure detector finds out (``auto_evict``). Its app is the
        caller's to kill; ``recover_replica`` brings a replacement into
        the row."""
        self._cut_off(self._lost | {r})
        self.runtimes[r].timer.stop()
        self.runtimes[r].log.info_wtime("LOST: row cut off, timer stopped")

    def _cut_off(self, lost: set) -> None:
        """Every replica of ``lost`` alone, the others together (all
        heard again where ``lost`` is empty); ``_lost`` follows only
        where the engine took the split."""
        if lost:
            self.cluster.partition(
                [[p for p in range(self.R) if p not in lost]]
                + [[p] for p in sorted(lost)])
        else:
            self.cluster.heal()
        self._lost = lost

    def prewarm_recovery(self) -> None:
        """Load the programs that a membership change and a snapshot
        recovery run (the config view's reads, the determinant's, the
        vote records', the install), so that the first one under load
        compiles nothing. Call before ``run()``; the state is left as
        it was."""
        self._mm.current(0)
        recover_vote(self.cluster.state, 0)
        # index 1 whatever was applied: the determinant's term is read
        # off the ring only for an index above 0
        snap = take_snapshot(self.cluster.state, 0, index=1)
        install_snapshot(self.cluster.state, 0, snap)   # result dropped

    def recover_replica(self, r: int, donor: Optional[int] = None,
                        timeout: float = 60.0,
                        wait_app: bool = True) -> None:
        """Snapshot-recover replica ``r`` from ``donor`` (default: current
        leader): install the consensus determinant and transfer the event
        history into r's stable store (reset first — never duplicated).
        The app instance behind r must be fresh (restarted) — its state is
        rebuilt by replaying the store. Executes inside the poll loop so
        it never races the stepping thread over cluster state; under a
        running loop the app is fed by a thread of its own
        (:meth:`_rebuild_app`) while the loop serves, and this call
        returns when the app holds the history, or with
        ``wait_app=False`` as soon as the consensus state and the store
        are in (a joiner must be asked into the configuration before it
        falls a window behind: ``wait_app_rebuilt`` is for afterwards)."""
        done = threading.Event()
        box: list = []
        with self._lock:
            if self._recover_req is not None:
                raise RuntimeError("a recovery request is already pending")
            self._recover_req = (r, donor, done, box)
        self._wake.set()
        if self._thread is None or not self._thread.is_alive():
            self.step()
        elif not done.wait(timeout):
            raise TimeoutError("recovery did not run (loop stalled?)")
        if box:
            raise box[0]
        if wait_app:
            self.wait_app_rebuilt(r, timeout)

    def wait_app_rebuilt(self, r: int, timeout: float = 60.0) -> None:
        """Return once no rebuild of replica ``r``'s app is under way
        (:meth:`_rebuild_app`), raising what ended it if it failed."""
        for t, rr, box in list(self._rebuilders):
            if rr != r:
                continue
            t.join(timeout)
            if t.is_alive():
                raise TimeoutError("the app's rebuild did not end")
            if box:
                raise box[0]

    def reset_app(self, r: int, timeout: float = 60.0) -> None:
        """Exit mis-speculation quarantine: the operator has restarted
        replica ``r``'s app FRESH; rebuild its state by replaying r's own
        committed store (complete — persistence continued while dirty)
        and resume live replay. Executes inside the poll loop."""
        done = threading.Event()
        box: list = []
        with self._lock:
            if self._reset_req is not None:
                raise RuntimeError("an app reset is already pending")
            self._reset_req = (r, done, box)
        self._wake.set()
        if self._thread is None or not self._thread.is_alive():
            self.step()
        elif not done.wait(timeout):
            raise TimeoutError("app reset did not run (loop stalled?)")
        if box:
            raise box[0]

    def _ckpt_path(self, r: int) -> Optional[str]:
        if self._workdir is None:
            return None
        return os.path.join(self._workdir, f"replica{r}.ckpt")

    def _read_ckpt(self, r: int):
        """-> (index, blob) of replica ``r``'s app checkpoint, or None."""
        path = self._ckpt_path(r)
        if path is None or not os.path.exists(path):
            return None
        import struct
        with open(path, "rb") as f:
            raw = f.read()
        if len(raw) < 8:
            return None
        return struct.unpack("<Q", raw[:8])[0], raw[8:]

    def checkpoint_app(self, r: int, timeout: float = 60.0) -> None:
        """Capture replica ``r``'s app state (follower only — a
        speculative leader's app runs AHEAD of commit) at its current
        store index, persist it, and compact the store prefix it covers.
        Executes inside the poll loop so the app/store pair is frozen at
        a consistent point."""
        done = threading.Event()
        box: list = []
        with self._lock:
            if self._ckpt_req is not None:
                raise RuntimeError("a checkpoint is already pending")
            self._ckpt_req = (r, done, box)
        self._wake.set()
        if self._thread is None or not self._thread.is_alive():
            self.step()
        elif not done.wait(timeout):
            raise TimeoutError("checkpoint did not run (loop stalled?)")
        if box:
            raise box[0]

    def _do_checkpoint(self, r: int) -> None:
        self._phase_prof.start("checkpoint")
        try:
            self._checkpoint(r)
        finally:
            self._phase_prof.stop("checkpoint")

    def _checkpoint(self, r: int) -> None:
        import struct
        rt = self.runtimes[r]
        if self.app_snapshot is None:
            raise RuntimeError("no app_snapshot hook configured")
        if rt.replay is None or rt.store is None:
            raise RuntimeError("replica has no app/store")
        if self._leader_view == r:
            raise RuntimeError(
                "checkpoint must come from a follower: a speculative "
                "leader's app state runs ahead of commit")
        if rt.app_dirty:
            raise RuntimeError("cannot checkpoint a dirty app")
        dump_fn = self.app_snapshot[0]
        probe_fn = (self.app_snapshot[2]
                    if len(self.app_snapshot) > 2 else None)
        # store[base, n) has been DELIVERED to the app's replay sockets
        # by the time we run (same poll-loop sweep), but delivery is not
        # consumption: a single-threaded event-loop app may service the
        # dump connection before draining replay bytes buffered on
        # other connections, and compact(n) would then drop records the
        # checkpoint does not cover. Barrier first: a protocol probe per
        # replay connection when the hook provides one, else kernel
        # queue quiescence (send-q + app rx-q empty).
        n = len(rt.store)
        if probe_fn is not None:
            rt.replay.barrier(probe_fn)
        elif not rt.replay.quiesce():
            raise RuntimeError(
                "app did not consume its replay stream (quiesce "
                "timeout); checkpoint aborted to protect compaction")
        with rt.replay.raw_conn() as s:
            blob = dump_fn(s)
        path = self._ckpt_path(r)
        atomic_write(path, struct.pack("<Q", n) + blob)
        rt.store.compact(n)
        self.obs.metrics.inc("checkpoints_total", replica=r)
        self.obs.trace.record(obs_trace.CHECKPOINT_TAKEN, replica=r,
                              record=n, blob_bytes=len(blob))
        rt.log.info_wtime(
            "CHECKPOINT: app state at record %d (%d bytes); store "
            "compacted" % (n, len(blob)))

    def _restore_ckpt(self, rt: _ReplicaRuntime, ckpt) -> None:
        restore_fn = self.app_snapshot[1]
        with rt.replay.raw_conn() as s:
            restore_fn(s, ckpt[1])

    def _do_reset_app(self, r: int) -> None:
        rt = self.runtimes[r]
        if rt.rebuild is not None:
            # a background rebuild's last leg: the few records that
            # reached the store since its worker looked, fed here,
            # where nothing appends to the store meanwhile (the
            # pipeline is drained); live replay takes over from the
            # next committed entry
            (cur, t0), rt.rebuild = rt.rebuild, None
            self._feed_store(rt, cur, len(rt.store))
            rt.app_dirty = False
            # the rebuild's waits for the fresh app are ``app_rebuild``'s,
            # not the next dispatch's ``replay_answer_wait``
            rt.replay.take_answer_waits()
            prof = self._phase_prof
            prof.credit("app_rebuild", (time.perf_counter() - t0) * 1e6)
            prof.count("replay_reconnects_total", len(rt.replay.conns))
            rt.log.info_wtime("APP REBUILT: holds the store's %d records"
                              % len(rt.store))
            return
        if rt.replay is not None:
            rt.replay.close()
            rt.replay = ReplayEngine("127.0.0.1", rt.app_port)
        if rt.store is not None and rt.replay is not None:
            if rt.store.base > 0:
                # the compacted prefix is covered by this replica's own
                # app checkpoint: restore it, then replay the suffix
                ckpt = self._read_ckpt(r)
                if (ckpt is None or ckpt[0] != rt.store.base
                        or self.app_snapshot is None):
                    raise RuntimeError(
                        "store compacted to %d but no matching app "
                        "checkpoint to rebuild from" % rt.store.base)
                self._restore_ckpt(rt, ckpt)
            replay_store_into(rt.store, rt.replay, start=0)
        rt.app_dirty = False
        rt.log.info_wtime("APP RESET: rebuilt from committed store")

    def _do_recover(self, r: int, donor: Optional[int],
                    app_fresh: bool = True, ledger=None,
                    min_verified: int = 1) -> None:
        """``app_fresh=False`` (the auto-recovery path) replays only the
        DELTA of the donor's history into r's still-running app — the
        app already executed its own store's prefix; a full replay would
        double-apply non-idempotent commands. ``ledger`` (the repair
        pipeline) makes the transfer DIGEST-VERIFIED: the snapshot
        carries the donor's audit-chain position and the install
        refuses a donor contradicting the ledger majority — raising
        BEFORE any state (device, store, or app) is touched."""
        self._phase_prof.start("recover")
        try:
            self._recover(r, donor, app_fresh, ledger, min_verified)
        finally:
            self._phase_prof.stop("recover")

    def _recover(self, r: int, donor: Optional[int], app_fresh: bool,
                 ledger, min_verified: int) -> None:
        donor = self._leader_view if donor is None else donor
        if donor < 0:
            raise RuntimeError("no donor available")
        drt, rrt = self.runtimes[donor], self.runtimes[r]
        blob = drt.store.dump() if drt.store else b""
        # the blob matches the donor's HOST apply counter; the device
        # apply can lag it by one step's echo — snapshot at the host's
        snap = take_snapshot(self.cluster.state, donor, blob,
                             index=int(self.cluster.applied[donor]),
                             digests=ledger is not None,
                             rebased_total=self.cluster.rebased_total)
        # restore election durability: newest vote among live peers'
        # records (read BEFORE install wipes r's rows) and r's HardState
        # file; current term floored at all of them
        vt, vf = recover_vote(self.cluster.state, r)
        hs = rrt.hard.load() if rrt.hard is not None else None
        cur_term = 0
        if hs is not None:
            cur_term = hs[0]
            if hs[1] > vt:
                vt, vf = hs[1], hs[2]
        # state surgery under the engine host lock: recovery runs on
        # drained serial iterations, but the lock makes the invariant
        # local — a concurrent submit/begin_* can never observe the
        # install half-applied (graftlint lock-discipline rider)
        with self.cluster._host_lock:
            self.cluster.state = install_snapshot(
                self.cluster.state, r, snap,
                voted_term=vt, voted_for=vf, cur_term=cur_term,
                ledger=ledger, min_verified=min_verified)
            self.cluster.applied[r] = snap.index
            rt_stream = self.cluster.replayed[r]
            rrt.replay_cursor = len(rt_stream)
            # undrained frames predate the snapshot load: appending
            # them to the freshly loaded store would duplicate history
            self.cluster.frames[r] = []
            if r in self._lost:
                # a replacement stands in the lost machine's row: heard
                # again only now that nothing of the old one is left
                self._cut_off(self._lost - {r})
                rrt.timer.beat()
        if rrt.store is not None and snap.store_blob:
            old_len = len(rrt.store)
            rrt.store.reset()
            n_loaded = rrt.store.load(snap.store_blob)
            self._phase_prof.count("recover_bytes_total",
                                   len(snap.store_blob))
            self._phase_prof.count("recover_entries_total", n_loaded)
            base = rrt.store.base
            if base > 0:
                # the donor's store was compacted behind its app
                # checkpoint: carry the checkpoint over so r (and any
                # later reset of r) can cover the missing prefix
                if self.app_snapshot is None:
                    raise RuntimeError(
                        "donor %d store is compacted (base %d) but no "
                        "app_snapshot hook is configured to restore its "
                        "checkpoint" % (donor, base))
                ckpt = self._read_ckpt(donor)
                if ckpt is None or ckpt[0] != base:
                    raise RuntimeError(
                        "donor %d store compacted to %d but no matching "
                        "app checkpoint" % (donor, base))
                import shutil
                if self._ckpt_path(r) is not None:
                    shutil.copyfile(self._ckpt_path(donor),
                                    self._ckpt_path(r))
                if app_fresh:
                    self._restore_ckpt(rrt, ckpt)
                elif old_len < base:
                    raise RuntimeError(
                        "live app executed only %d records but the "
                        "donor history now starts at %d — restart the "
                        "app and use reset_app" % (old_len, base))
            # fresh app: rebuild checkpoint + full retained history;
            # live app (auto recovery): deliver only the records beyond
            # the prefix it already executed — its own old store (a
            # prefix of the donor's, both being the committed order)
            if not app_fresh:
                replay_store_into(rrt.store, rrt.replay, start=old_len)
            elif rrt.replay is not None:
                # the old engine's sockets were the old app's
                rrt.replay.close()
                rrt.replay = ReplayEngine("127.0.0.1", rrt.app_port)
                if threading.current_thread() is self._thread:
                    self._start_rebuild(r, rrt, snap.store_blob)
                else:
                    self._feed_blob(rrt, snap.store_blob)
                    rrt.replay.drain_responses()

    # a rebuild's worker hands over to the poll loop once the store is
    # at most this many records ahead of what it has fed the app
    REBUILD_HANDOVER = 64

    def _feed_blob(self, rt: _ReplicaRuntime, blob: bytes) -> int:
        """A fresh app fed the history a joiner's snapshot brought, from
        the blob itself; -> the store index after its last record."""
        base, records = dump_records(blob)
        n = 0
        for n, rec in enumerate(records, 1):
            apply_record(rt.replay, rec)
        return base + n

    def _feed_store(self, rt: _ReplicaRuntime, start: int,
                    stop: int) -> None:
        """Records ``[start, stop)`` of ``rt``'s own store into its app
        (what committed since the blob was taken)."""
        replay_store_into(rt.store, rt.replay, start=start, stop=stop,
                          cap=self.cfg.slot_bytes + 64)

    def _start_rebuild(self, r: int, rt: _ReplicaRuntime,
                       blob: bytes) -> None:
        """Under a running loop a fresh app is fed by a thread of its
        own: the whole history at an answer a request would hold the
        loop, and with it every client, for seconds. Until it holds
        the history the app is quarantined (``app_dirty``: the store
        keeps every committed entry, the app is replayed nothing and
        serves nobody)."""
        rt.app_dirty = True
        box: list = []
        t = threading.Thread(target=self._rebuild_app,
                             args=(r, rt, blob, box), daemon=True)
        self._rebuilders = [e for e in self._rebuilders
                            if e[0].is_alive()] + [(t, r, box)]
        t.start()

    def _rebuild_app(self, r: int, rt: _ReplicaRuntime, blob: bytes,
                     box: list) -> None:
        """The worker: the blob, then what the store has gained since,
        again and again until it is within ``REBUILD_HANDOVER`` records
        of the store's end; the last leg is the poll loop's
        (``_do_reset_app``, asked for like an operator's reset), since
        only there nothing appends meanwhile. Ends when that is done."""
        t0 = time.perf_counter()
        try:
            cur = self._feed_blob(rt, blob)
            while not self._stop.is_set():
                end = len(rt.store)
                if end - cur <= self.REBUILD_HANDOVER:
                    break
                self._feed_store(rt, cur, end)
                cur = end
            rt.rebuild = (cur, t0)
            done = threading.Event()
            while not self._stop.is_set():
                with self._lock:
                    if self._reset_req is None:
                        self._reset_req = (r, done, box)
                        break
                time.sleep(0.001)
            self._wake.set()
            while not done.wait(0.05):
                if self._stop.is_set() or self.loop_error is not None:
                    raise RuntimeError("the loop ended under the rebuild")
        except Exception as exc:  # noqa: BLE001 — reported to the waiter
            # the app stays quarantined; reset_app starts over
            rt.rebuild = None
            box.append(exc)
            rt.log.info_wtime("APP REBUILD FAILED: %r" % (exc,))

    def _apply_new_entries(self, r: int, rt: _ReplicaRuntime,
                           replays: list) -> None:
        """Persist replica ``r``'s newly committed entries, release the
        acks of its own ones and plan what its app is to be replayed:
        ``(engine, ops)`` is appended to ``replays``, which
        :meth:`_replay_in_turns` delivers once every replica's turn is
        done."""
        stream = self.cluster.replayed[r]
        n = len(stream)
        if rt.replay_cursor >= n:
            return
        cur, rt.replay_cursor = rt.replay_cursor, n
        remote: list = []
        self._apply_stream(r, rt, stream, cur, self.cluster.frames,
                           rt.inflight, r, remote)
        if remote:
            replays.append((rt.replay, remote))

    def _apply_stream(self, r: int, rt: _ReplicaRuntime, stream, cur: int,
                      frames: list, inflight: collections.deque,
                      span_rep: int, remote: list) -> int:
        """One committed stream's entries from ``cur`` on, as replica
        ``r`` holds them (the group's, or one of the sharded driver's G
        a replica): ``frames[r]`` to the store, the acks of its own
        entries released off ``inflight``, what its app is to be
        replayed appended to ``remote``. -> acks released."""
        prof = self._phase_prof
        prof.start("apply_replay_ack")
        # the engine's decode left the new entries as COLUMNAR batches
        # (hostpath.ReplayBatch): the replay/ack sweep below touches
        # Python O(1) per window, not O(1) per entry
        segs = (stream.segments_from(cur)
                if hasattr(stream, "segments_from")
                else [stream[cur:]])
        if rt.store is not None:
            # frames were assembled vectorized during the window decode
            # (SimCluster.collect_frames); one syscall appends the batch
            blobs = frames[r]
            if blobs:
                prof.start("store_append")
                frames[r] = []
                for b in blobs:
                    rt.store.append_framed(b)
                prof.stop("store_append")
        # a dirty app's state diverged: keep persisting (the store stays
        # the complete committed stream) but feed the app nothing until
        # reset_app rebuilds it
        replaying = rt.replay is not None and not rt.app_dirty
        own_max = -1

        def own_of(conns, _gens):
            return conn_origin(conns) == r

        prof.start("replay_send")
        for seg in segs:
            # remote SEND runs arrive coalesced per connection (one
            # loopback write per run — byte-stream identical for the
            # app); CONNECT/CLOSE apply individually
            seg_max, ops, _n_rem = plan_segment(seg, own_of,
                                                want_ops=replaying)
            own_max = max(own_max, seg_max)
            remote.extend(ops)
        prof.stop("replay_send")
        released = 0
        if rt.store is not None:
            # The WRITE precedes the ack (store_record runs inside the
            # reference's apply, before the proxy releases the client,
            # db-interface.c:65-96) — but the reference never fsyncs per
            # record: its durability contract is replication to a
            # QUORUM'S MEMORY plus an OS-buffered store write. Matching
            # that, fdatasync runs on a cadence (and at close/snapshot),
            # not on the ack path — a per-batch fsync was a measurable
            # share of the shared-core budget and bought durability the
            # reference never promised.
            now = time.monotonic()
            if now - rt.last_sync > self.sync_period:
                rt.store.sync()
                rt.last_sync = now
        if own_max >= 0:
            # ack release by sequence: every own-origin entry carries
            # the fragment seq in req_id (monotone in commit order), so
            # commits are matched exactly even across leadership churn
            prof.start("ack_release")
            releases = []
            with self._lock:
                while inflight and inflight[0][1] <= own_max:
                    ev, seq = inflight.popleft()
                    releases.append((ev, seq))
            released = len(releases)
            # spans first so the latency observe below can attach the
            # SAMPLED releases' span ids as histogram exemplars
            sampled = {}
            if releases:
                self.obs.trace.record(obs_trace.PROXY_ACK_RELEASE,
                                      replica=r, count=len(releases),
                                      submit_seq=own_max)
                sampled = {req: conn for conn, req
                           in self.obs.spans.ack_release(span_rep,
                                                         own_max)}
            now = time.perf_counter()
            t0_sum = 0.0
            for ev, seq in releases:
                ev.release(0)
                t0_sum += ev.t0
                # intake→release is the client-visible commit latency
                # (the spin at proxy.c:160, measured instead of spun)
                self.obs.metrics.observe(
                    "commit_latency_seconds", now - ev.t0,
                    buckets=LATENCY_BUCKETS_S,
                    exemplar=(span_trace_id(sampled[seq], seq)
                              if seq in sampled else None),
                    replica=r)
            if releases:
                # the same intake -> release, as an exact sum
                prof.credit("intake_to_ack",
                            (len(releases) * now - t0_sum) * 1e6,
                            len(releases))
            prof.stop("ack_release")
        prof.stop("apply_replay_ack")
        return released

    def _replay_in_turns(self, replays: list) -> None:
        """Deliver each follower's planned operations to its app, one
        operation a follower in turn, and count what was delivered and
        what the apps answered. A ``ReplayEngine`` waits for its app's
        answer before its next write (log order across connections is
        what keeps the apps equal); taken in turns, that answer is
        produced while the other followers are written to."""
        if not replays:
            return
        prof = self._phase_prof
        prof.start("apply_replay_ack")
        prof.start("replay_send")
        for turn in itertools.zip_longest(*(ops for _, ops in replays)):
            for (engine, _), op in zip(replays, turn):
                if op is not None:
                    try:
                        engine.apply(*op)
                    except OSError as exc:
                        self._replay_lost(engine, exc)
        prof.stop("replay_send")
        prof.count("replay_applies_total",
                   sum(len(ops) for _, ops in replays))
        prof.count("replay_followers_total", len(replays))
        prof.start("replay_drain")
        reply_bytes = sum(engine.drain_responses() for engine, _ in replays)
        prof.stop("replay_drain")
        prof.count("replay_reply_bytes_total", reply_bytes)
        # of ``replay_send``, the followers' apps' turn: what the engines
        # blocked for in ``_settle``, credited once and not timed an apply
        waits = [engine.take_answer_waits() for engine, _ in replays]
        n_waits = sum(n for n, _ in waits)
        if n_waits:
            prof.credit("replay_answer_wait",
                        sum(ns for _, ns in waits) / 1e3, n_waits)
        done = [engine.take_replayed() for engine, _ in replays]
        prof.count("replay_requests_total", sum(n for n, _ in done))
        prof.count("replay_order_timeouts_total", sum(t for _, t in done))
        proven = [engine.take_answers() for engine, _ in replays]
        prof.count("replay_answers_total", sum(a for a, _ in proven))
        prof.count("replay_unproven_handoffs_total",
                   sum(u for _, u in proven))
        prof.stop("apply_replay_ack")

    def _replay_lost(self, engine: ReplayEngine, exc: OSError) -> None:
        """A follower's app would not take a replayed operation (it
        died, or reset the connection): the pass goes on for the
        others, and this app is quarantined like a mis-speculated one
        (``app_dirty``): its store keeps every committed entry, it is
        replayed nothing more, and ``recover_replica`` / ``reset_app``
        rebuild a fresh one. Never the loop's death: the group serves
        on without it."""
        for rt in self.runtimes:
            if rt.replay is engine and not rt.app_dirty:
                rt.app_dirty = True
                engine.close()
                self.obs.metrics.inc("replay_errors_total", replica=rt.idx)
                rt.log.info_wtime("APP LOST: replay failed (%r); "
                                  "quarantined until rebuilt" % (exc,))

    # ------------------------------------------------------------------
    # lifecycle
    # ------------------------------------------------------------------

    def _handle_loop_crash(self, exc: BaseException) -> None:
        """A raised step must never silently kill the poll thread with
        app threads parked on commit waits: record it, fail every
        blocked event so the apps sever/retry, and stop the loop."""
        import traceback
        self.loop_error = exc
        traceback.print_exc()
        self.obs.metrics.inc("loop_errors_total")
        with self._lock:
            for rt in self.runtimes:
                self._fail_inflight_locked(rt, "poll-loop crash")
        if self._workdir is not None:
            # post-mortem: persist the protocol trace ring next to the
            # replica logs
            try:
                self.obs.trace.dump_on_failure(
                    os.path.join(self._workdir, "trace_dump.json"),
                    reason=f"poll-loop crash: {exc!r}")
            except OSError:
                pass

    def _busy(self) -> bool:
        with self._lock:
            return bool(any(self._submitq)
                        or any(len(q) for q in self.cluster.pending)
                        or self._waiter_count()
                        # queued reads need steps to confirm/serve —
                        # keep the loop running until they resolve
                        or (self.cluster.reads is not None
                            and self.cluster.reads.pending_count())
                        # in-flight transactions decide off the
                        # finish() tail — keep stepping until then
                        or (self.cluster.txn is not None
                            and self.cluster.txn.wants_serial()))

    # holds-lock: _lock
    def _waiter_count(self) -> int:
        """Blocked commit waiters across replicas (caller holds
        ``_lock``); the sharded driver counts its per-group deques."""
        return sum(len(rt.inflight) for rt in self.runtimes)

    def _pipeline_ready(self) -> bool:
        """True iff the next iteration may DISPATCH WITHOUT FINISHING —
        the stable-leader traffic path where pipelining is a pure
        latency/throughput transform. Everything else (elections,
        admin requests, recovery, rebase drains, idle heartbeats)
        drains the pipeline and runs the serial ``step()``."""
        if (self._recover_req is not None or self._reset_req is not None
                or self._ckpt_req is not None):
            return False
        c = self.cluster
        if c.last is None or self._leader_view < 0:
            return False
        if c.need_recovery or self.stepped_down:
            return False
        # a membership change in flight polls device-side config state
        # every step — drive it through drained serial steps
        if self._config_phase is not None:
            return False
        # a due repair action needs the drained serial path (snapshot
        # install + redigest are state surgery); pipelining re-engages
        # the iteration after the repair completes
        if self.repair is not None and self.repair.needs_drain():
            return False
        # stop dispatching once the i32-rollover threshold is crossed:
        # the rebase is deferred until the pipeline drains, and the
        # headroom margin covers only boundedly many in-flight bursts
        if int(c.last["end"].max()) >= self.cfg.rebase_threshold:
            return False
        # an in-flight transaction holds the commit lane: votes and
        # decision records ride SERIAL dispatches only (the same
        # give-way rule elections and repair follow)
        if c.txn is not None and c.txn.wants_serial():
            return False
        # an open topology transition window runs its passes on the
        # drained serial path every iteration (seed → freeze →
        # cutover) — hold pipelining for the whole window
        topo = getattr(c, "topology", None)
        if topo is not None and topo.needs_drain():
            return False
        # the governor engages/disengages depth-D pipelining: until
        # backlog has STOOD for engage_evals (or while shedding), the
        # serial path acks a commit one dispatch sooner
        if (self.governor is not None
                and not self.governor.decision.pipeline):
            return False
        # pipelining pays off only while APPEND BATCHES flow (encode
        # k+1 while k runs); with just blocked waiters and an empty
        # queue the serial loop acks a commit one dispatch sooner —
        # keeping the latency-bound regime on the serial path is what
        # makes pipelining a pure win, not a latency trade
        with self._lock:
            if not (any(self._submitq) or self._backlog()):
                return False
        # any expired follower election timer needs the serial path
        # (bursts and pipelined steps never fire timeouts)
        last = c.last
        for r, rt in enumerate(self.runtimes):
            if (not self._role_is_leader(last, r)
                    and rt.timer.expired()):
                return False
        return True

    def _role_is_leader(self, res, r: int) -> bool:
        return bool(res["role"][r] == int(Role.LEADER))

    # ------------------------------------------------------------------
    # idle quiescence (the PR 8 idle-dispatch bias, closed at source)
    # ------------------------------------------------------------------

    def _repair_idle(self) -> bool:
        """True iff the repair pipeline has nothing in flight: no due
        drain, no owned recoveries, no replica held in quarantine or
        probation (held replicas need steps to advance their
        hysteresis)."""
        if self.repair is None:
            return True
        if self.repair.needs_drain() or self.repair.owned():
            return False
        return not self._repair_held_any()

    def _repair_held_any(self) -> bool:
        return bool(self.repair.blocked_replicas(0))

    def _idle_margin(self) -> float:
        """Seconds until the earliest follower election timer would
        fire. The idle loop must dispatch a heartbeat step well before
        that — each step carries the heartbeat, so stepping IS the
        beat. The sharded driver overrides this: its group timers are
        step-domain and only tick for leaderless groups, which the
        skip gate already excludes."""
        last = self.cluster.last
        m = float("inf")
        for r, rt in enumerate(self.runtimes):
            if self._role_is_leader(last, r):
                continue
            m = min(m, rt.timer.remaining())
        return m

    def _can_idle_skip(self) -> bool:
        """True iff this iteration may skip the device dispatch
        entirely: a led, healthy, traffic-free cluster with no admin /
        repair / config work due and every follower election timer
        comfortably far from firing. Conservative by construction —
        any doubt dispatches the step."""
        if not self._idle_quiesce:
            return False
        c = self.cluster
        if c.last is None or self._leader_view < 0:
            return False
        # chaos drills (attached link models) own their own timing —
        # getattr both ways: SimCluster has link_model, ShardedCluster
        # has a per-group link_models dict
        if (getattr(c, "link_model", None) is not None
                or getattr(c, "link_models", None)):
            return False
        # an active profiler capture wants the serving path visible
        if self.profile_session is not None and self.profile_session.active:
            return False
        with self._lock:
            if (self._recover_req is not None
                    or self._reset_req is not None
                    or self._ckpt_req is not None):
                return False
        if self._config_phase is not None:
            return False
        if c.need_recovery or self.stepped_down:
            return False
        if not self._repair_idle():
            return False
        if self._busy():
            return False
        return self._idle_margin() > self._idle_guard

    def _idle_park(self) -> None:
        """One idle-quiescence beat: count the avoided dispatch, keep
        the alert/health cadences fresh, and park with exponential
        backoff — bounded well inside the follower-timer margin, and
        broken instantly by any intake event (``_wake``)."""
        self.obs.metrics.inc("idle_dispatches_avoided_total")
        if self._idle_backoff <= 0.001:
            # once per quiescence episode, not per beat
            self.obs.trace.record(obs_trace.IDLE_QUIESCE)
        prof = self._phase_prof
        prof.start("observe")
        self._cadence_observe()
        prof.stop("observe")
        wait = min(self._idle_backoff, self._idle_margin() / 2)
        self._idle_backoff = min(self._idle_backoff * 2,
                                 self._idle_backoff_max)
        prof.start("idle_wait")
        self._wake.wait(timeout=max(wait, 0.0005))
        prof.stop("idle_wait")
        self._wake.clear()

    def _drain_pipeline(self) -> bool:
        """Block until the readback thread retired every in-flight
        ticket (device outputs read AND post-step host rules run).
        True when drained; False when the loop died."""
        with self._pl_cv:
            while self._pl_pending:
                if self.loop_error is not None:
                    return False
                if (self._rb_thread is not None
                        and not self._rb_thread.is_alive()):
                    return False
                self._pl_cv.wait(timeout=0.05)
        return self.loop_error is None

    def _readback_loop(self) -> None:
        """Consumer half of the pipelined driver: finish tickets in
        dispatch (FIFO) order and run every post-step host rule —
        including observability export — OFF the dispatch path."""
        while True:
            ticket = self._pl_queue.get()
            if ticket is None:
                return
            # this thread's unit of the phase account: one ticket
            self._phase_prof.start("cycle")
            try:
                res = self.cluster.finish(ticket)
                self._post_step(res)
            except Exception as exc:  # noqa: BLE001
                self._handle_loop_crash(exc)
                with self._pl_cv:
                    self._pl_pending = 0
                    self._pl_cv.notify_all()
                return
            finally:
                self._phase_prof.stop("cycle")
            with self._pl_cv:
                self._pl_pending -= 1
                self._pl_cv.notify_all()

    def _dispatch_loop(self, period: float) -> None:
        """One ``cycle`` of the phase profiler per iteration: whatever
        an iteration spends outside a named phase is its
        ``unattributed``. (The readback thread makes a cycle of each
        ticket; while it does, this thread's is in ``pipeline_wait``.)"""
        prof = self._phase_prof
        while not self._stop.is_set() and self.loop_error is None:
            prof.start("cycle")
            try:
                if not self._loop_once(period):
                    return
            finally:
                prof.stop("cycle")

    def _loop_once(self, period: float) -> bool:
        """One iteration of the dispatch loop; False ends the loop."""
        prof = self._phase_prof
        prof.start("dispatch_gate")
        pipelined = self.pipeline >= 2 and self._pipeline_ready()
        prof.stop("dispatch_gate")
        if not pipelined:
            # serial iteration (elections / admin / recovery / rebase /
            # idle heartbeat): drain first — the engine's FIFO finish
            # contract forbids a fused step() while tickets are in
            # flight
            prof.start("pipeline_wait")
            drained = self._drain_pipeline()
            prof.stop("pipeline_wait")
            if not drained:
                return False
        if self._stop.is_set():
            return False
        if not pipelined:
            # the idle-skip check and the step share one crash
            # handler: a raised skip-path bug must fail blocked
            # waiters loudly, never park the loop dead silently
            try:
                prof.start("dispatch_gate")
                idle = self._can_idle_skip()
                prof.stop("dispatch_gate")
                if idle:
                    # idle quiescence: nothing needs the device —
                    # skip the dispatch, keep the cadences live
                    self._idle_park()
                    return True
                self._idle_backoff = 0.001  # re-arm the backoff
                self.step()
            except Exception as exc:  # noqa: BLE001
                self._handle_loop_crash(exc)
                return False
            prof.start("dispatch_gate")
            busy = self._busy()
            prof.stop("dispatch_gate")
            if not busy and period:
                prof.start("idle_wait")
                self._wake.wait(timeout=period)
                prof.stop("idle_wait")
            self._wake.clear()
            return True
        # ---- pipelined fast path: encode + dispatch only ----
        with self._pl_cv:
            if self._pl_pending >= self.pipeline:
                prof.start("pipeline_wait")
                self._pl_cv.wait(timeout=0.05)
                prof.stop("pipeline_wait")
                return True
        prof.start("admin_pump")
        self._pump_submitq()
        prof.stop("admin_pump")
        dec = (self.governor.decision if self.governor is not None
               else None)
        if (dec is not None and dec.coalesce_us > 0
                and self._backlog()):
            # bounded admission-coalescing wait (governor): at a
            # high arrival rate with a window still filling, a
            # beat of patience ships fuller windows — strictly
            # bounded, never applied while shedding
            time.sleep(dec.coalesce_us / 1e6)
            self.obs.metrics.observe(
                "governor_coalesce_us", dec.coalesce_us,
                buckets=LATENCY_BUCKETS_US)
            self._pump_submitq()
        try:
            # dec.max_k can flip to 1 (SLO shed) between
            # _pipeline_ready and here: honor it with a no-take
            # heartbeat dispatch — never a burst; the next
            # iteration sees pipeline disengaged and drains to
            # the serial path
            if self._backlog() and (dec is None or dec.max_k > 1):
                ticket = self.cluster.begin_burst(
                    max_k=dec.max_k if dec is not None else None)
            else:
                # waiters with empty queues: quorum/commit trails
                # the last append by a step — advance it (no batch
                # take: pipelined appends ride capacity-clamped
                # bursts only, so shortfall requeues cannot reorder
                # against in-flight dispatches)
                ticket = self.cluster.begin_step(take_batch=False)
        except Exception as exc:  # noqa: BLE001
            self._handle_loop_crash(exc)
            return False
        with self._pl_cv:
            self._pl_pending += 1
        self._pl_queue.put(ticket)
        return True

    def run(self, period: float = 0.0) -> None:
        """Run the polling loop in background threads. While client work
        is pending or blocked app threads await commit, the loop
        free-runs (the reference's busy commit loop). When idle it
        PARKS for up to ``period`` seconds (the hb_period cadence — each
        step carries the heartbeat, so ``period`` must stay well under
        the election timeout) and wakes INSTANTLY when a link thread
        hands it an event — on a shared-core host, idle free-running
        would steal the CPU the app itself needs.

        With ``pipeline >= 2`` (the default) the stable-leader traffic
        path runs DOUBLE-BUFFERED: the dispatch thread encodes and
        enqueues batch k+1 while batch k is still running on the
        device, and the readback thread blocks on outputs and runs the
        post-step host rules (requeue, replay, acks, observability) —
        ``device_sync`` never blocks the enqueue path. ``pipeline=0``
        (or 1) restores the fully serial loop."""
        self._pl_pending = 0
        self._rb_thread = threading.Thread(target=self._readback_loop,
                                           daemon=True)
        self._rb_thread.start()

        def loop():
            try:
                self._dispatch_loop(period)
            finally:
                self._pl_queue.put(None)     # retire the readback side
        self._thread = threading.Thread(target=loop, daemon=True)
        self._thread.start()

    def prewarm(self) -> None:
        """AOT-warm every step variant and burst tier so the first loaded
        round never eats a multi-second JIT pause mid-serving."""
        self.cluster.prewarm()

    def stop(self, join_timeout: float = 5.0) -> None:
        # idempotent: tests (and death-path drills) may stop explicitly
        # and again from fixture teardown — the second call must not
        # touch already-closed native handles
        if getattr(self, "_stopped", False):
            return
        self._stop.set()
        self._wake.set()
        # the ops exporter and series log are independent of the poll
        # thread — close them first so a wedged loop still leaves a
        # flushed series.jsonl and a closed port behind
        if self.exporter is not None:
            self.exporter.close()
        if self.series is not None:
            self.series.close()
        with self._pl_cv:
            self._pl_cv.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=join_timeout)
            if self._thread.is_alive():
                # a wedged poll thread (e.g. blocked inside a device
                # step) may still be touching the native handles:
                # closing them under it would be a use-after-free.
                # Leak them loudly instead; a later stop() retries.
                # But FIRST fail every blocked commit waiter: with
                # _stop set no step will ever release them, so app
                # threads parked in proxy_call commit waits would hang
                # forever instead of failing fast with -1 (releasing a
                # PendingEvent is pure host state — safe regardless of
                # what the wedged thread is doing; a concurrent release
                # from it is an idempotent no-op) — ADVICE.md #4.
                if self.cluster.reads is not None:
                    self.cluster.reads.fail_all(
                        "stop (wedged poll thread)")
                if self.cluster.streams is not None:
                    self.cluster.streams.fail_all(
                        "stop (wedged poll thread)")
                with self._lock:
                    n = sum(len(rt.inflight) for rt in self.runtimes)
                    for rt in self.runtimes:
                        self._fail_inflight_locked(
                            rt, "stop (wedged poll thread)")
                self.obs.trace.record(obs_trace.STOP_FORCED,
                                      released=n)
                if self._workdir is not None:
                    try:
                        self.obs.trace.dump_on_failure(
                            os.path.join(self._workdir,
                                         "trace_dump.json"),
                            reason="stop: wedged poll thread")
                    except OSError:
                        pass
                self.runtimes[0].log.info_wtime(
                    "STOP: poll thread did not exit within %gs; "
                    "released %d inflight waiters with -1; leaving "
                    "native handles open" % (join_timeout, n))
                return
        if self._rb_thread is not None:
            self._pl_queue.put(None)
            self._rb_thread.join(timeout=join_timeout)
        for t, _r, _box in self._rebuilders:
            t.join(timeout=join_timeout)
        # release commit waiters that were already inflight at stop —
        # nothing will ever step again, so they must fail, not hang
        # (queued reads the same: no step will ever confirm them)
        if self.cluster.reads is not None:
            self.cluster.reads.fail_all("stop")
        # watchers/scans the same: the pump must quiesce and every
        # blocked subscriber poll must fail fast (clients resume
        # elsewhere with their tokens); flushes the CDC sink
        if self.cluster.streams is not None:
            self.cluster.streams.fail_all("stop")
        with self._lock:
            for rt in self.runtimes:
                self._fail_inflight_locked(rt, "stop")
        try:
            for rt in self.runtimes:
                # one replica's close failure must not leak the rest
                for res in (rt.proxy, rt.replay, rt.store, rt.log):
                    if res is None:
                        continue
                    try:
                        res.close()
                    except OSError:
                        pass
        finally:
            # latch only after the cleanup actually ran
            self._stopped = True

    def leader(self) -> int:
        with self._lock:
            return self._leader_view

    # ------------------------------------------------------------------
    # the linearizable read queue (runtime/reads.py)
    # ------------------------------------------------------------------

    def read_replica(self, group: int = 0) -> int:
        """The replica a linearizable read should target: the group's
        lease-serving holder (zero-traffic path) when one exists, else
        the leader (read-index path), else replica 0 (the hub confirms
        before serving, so a bad default only costs latency)."""
        lm = self.cluster.leases
        r = lm.serving_holder(group) if lm is not None else -1
        if r < 0:
            r = self.leader()
        return r if r >= 0 else 0

    def read(self, fn=None, *, replica: Optional[int] = None,
             group: int = 0, timeout: float = 30.0):
        """Queue one linearizable read and block until it serves (or
        fails). ``fn()`` runs AT the linearization point — on the
        readback thread, against the serving replica's applied state —
        and its return value lands on the returned ticket. Reads never
        enter ``begin_*``/``finish`` and never consume ring slots; an
        idle loop is woken so the confirming step dispatches
        immediately."""
        hub = self.cluster.reads
        if hub is None:
            raise RuntimeError(
                "driver was built with leases=False — no read path")
        if replica is None:
            replica = self.read_replica(group)
        t = hub.submit(fn, replica=replica, group=group)
        self._wake.set()
        t.wait(timeout)
        return t

    def can_serve_read(self, r: int) -> bool:
        """Read-index check: True iff replica ``r`` verified its
        leadership against a majority on the latest step, so a read of
        state at its commit index is linearizable (the reference verifies
        before answering pending reads — ep_dp_reply_read_req,
        dare_ep_db.c:132-161)."""
        last = self.cluster.last
        return (last is not None
                and bool(last["leadership_verified"][r]))
