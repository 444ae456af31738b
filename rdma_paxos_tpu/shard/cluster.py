"""Sharded multi-group consensus cluster — G independent Raft groups,
ONE compiled dispatch per step.

``SimCluster`` drives one consensus group; production-scale serving
partitions the keyspace across many. This engine stacks G independent
``(Log, HardState, peer_mask, timers)`` pytrees along a leading
``group`` axis and steps ALL of them with the group-batched protocol
step (:func:`rdma_paxos_tpu.consensus.step.group_step` — a second
``vmap`` over groups around the named replica-axis ``vmap``), the way
SmartNIC replication stacks multiplex many replicated partitions onto
one device (PAPERS.md, arXiv:2503.18093). Device work per step is one
program of G× the single-group tensor shapes; host work (commit/apply
frontiers, replay, requeue, rebase, leader tracking) stays per-group.

Two execution engines behind ONE host-bookkeeping implementation:

* ``mesh=None`` (default) — the single-device engine: the group axis
  is a ``vmap`` batch axis, all G×R state on one chip.
* ``mesh=(group_shards, R)`` (or a prebuilt 2-D ``Mesh``) — the
  MULTI-CHIP engine: state is sharded ``P(group, replica)`` over a
  real ``(group, replica)`` device mesh
  (:func:`~rdma_paxos_tpu.parallel.mesh.build_mesh_2d`) and the step
  compiles via ``shard_map``
  (:func:`~rdma_paxos_tpu.parallel.mesh.build_spmd_group_step`).
  Replica collectives bind the ``replica`` mesh axis; nothing crosses
  the group axis — aggregate committed-ops/s scales with the group
  shards because each added device row carries whole extra groups
  (``benchmarks/shard_bench.py --mesh`` measures the scaling
  efficiency). The ticket contract (``begin_*``/``finish``), replay,
  rebase, and chaos hooks are byte-for-byte the same host code.

Single-group is the G=1 special case, not a parallel code path: the
same ``replica_step`` core, the same host bookkeeping rules, the same
shared compile cache (``runtime/sim.py:STEP_CACHE``) —
``tests/test_shard.py`` pins bit-identical G=1 ≡ ``SimCluster``
behavior on a recorded workload.

Fault domains: every group has its own ``peer_mask[g]`` (and optional
per-group chaos ``LinkModel``), its own elections, its own rebase
clock. Crashing one group's leader cannot disturb any other group —
the fault-isolation property the shard nemesis proves.

Leader placement: G leaderships piling onto replica 0 would make one
host the leader for every shard; :meth:`place_leaders` spreads them
round-robin (or least-loaded) across the R replicas via targeted
election timeouts.
"""

from __future__ import annotations

import collections
import threading
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
import numpy as np

from rdma_paxos_tpu.config import LogConfig, REBASE_STALL_STEPS
from rdma_paxos_tpu.consensus.log import (
    EntryType, M_CONN, M_GIDX, M_LEN, M_REQID, M_TYPE)
from rdma_paxos_tpu.consensus.state import Role
from rdma_paxos_tpu.consensus.step import arg_layout
from rdma_paxos_tpu.obs.spans import held
from rdma_paxos_tpu.parallel.mesh import (
    GROUP_AXIS, REPLICA_AXIS, build_mesh_2d, build_sim_group_burst,
    build_sim_group_scan, build_sim_group_step, build_spmd_group_burst,
    build_spmd_group_scan, build_spmd_group_step, group_sharding,
    stack_group_states)
from rdma_paxos_tpu.runtime.hostpath import LazyReplayStream
from rdma_paxos_tpu.runtime.sim import (
    STEP_CACHE, ReplayFetch, SimCluster, StagingPool, StepTicket, cap_tiers,
    clamp_burst_take, count_ring, decode_window, make_put, pack_rows,
    read_scalars, rebase_delta_of, requeue_shortfall, require_drained)
from rdma_paxos_tpu.shard.router import KeyRouter

TimeoutsLike = Union[None, Dict[int, Sequence[int]],
                     Sequence[Tuple[int, int]]]


class ShardedCluster:
    """G-group × R-replica protocol simulation, one dispatch per step.

    Host-bookkeeping parity ledger vs ``SimCluster`` (the per-group
    rules are the same ones, widened by a group index; any change to
    SimCluster's step/requeue/replay/rebase logic must be mirrored
    here — the G=1 bit-equivalence test in ``tests/test_shard.py``
    catches drift in everything it exercises): ``collect_frames`` /
    ``frames`` (store-ready frame assembly) and the
    ``StepPhaseProfiler`` hooks now have full parity (phase
    histograms additionally carry ``{group=g}`` apply attribution);
    ``audit=True`` mirrors SimCluster's digest auditing with
    ``(group, term, index)`` ledger keys. Unifying the two engines'
    host bookkeeping behind one helper is a ROADMAP open item."""

    K_TIERS = SimCluster.K_TIERS
    REBASE_STALL_STEPS = REBASE_STALL_STEPS

    def __init__(self, cfg: LogConfig, n_replicas: int, n_groups: int,
                 *, router: Optional[KeyRouter] = None,
                 use_pallas: Optional[bool] = None,
                 interpret: bool = False, fanout: str = "gather",
                 stable_fast_path: bool = True,
                 group_size: Optional[int] = None,
                 audit: bool = False, flight_capacity: int = 64,
                 mesh=None, telemetry: bool = False,
                 scan: bool = False, txn: bool = False):
        if n_groups < 1:
            raise ValueError("n_groups must be >= 1")
        self.cfg = cfg
        # device-resident K-window scan tier (see SimCluster.scan):
        # burst dispatches ride the fused-scan program with ONE
        # consolidated readback + in-dispatch replay rows for all
        # G x R logs. Mutable at runtime; scan-off clusters build no
        # scan programs (cache keys untouched).
        self.scan = bool(scan)
        self.scan_dispatches = 0
        self.R = int(n_replicas)
        self.G = int(n_groups)
        self.group_size = group_size or n_replicas
        self.router = (router if router is not None
                       else KeyRouter(self.G))
        if use_pallas is None:
            use_pallas = jax.default_backend() == "tpu"
        self._use_pallas = use_pallas
        self._interpret = interpret
        self._fanout = fanout
        self._stable_fast_path = stable_fast_path
        # mesh engine: a 2-D (group, replica) device mesh — groups
        # sharded across chips, replica collectives named on the other
        # axis. None = the single-device vmap engine (unchanged). A
        # (group_shards, replicas) tuple builds the mesh here; a
        # prebuilt jax.sharding.Mesh is used as-is. Host bookkeeping is
        # IDENTICAL either way — only the compiled dispatch differs.
        if isinstance(mesh, tuple):
            mesh = build_mesh_2d(*mesh)
        if mesh is not None:
            names = tuple(mesh.axis_names)
            if names != (GROUP_AXIS, REPLICA_AXIS):
                raise ValueError(
                    f"mesh axes must be ({GROUP_AXIS!r}, "
                    f"{REPLICA_AXIS!r}), got {names}")
            shape = mesh.devices.shape
            if shape[1] != self.R:
                raise ValueError(
                    f"mesh replica axis is {shape[1]} devices but the "
                    f"cluster has {self.R} replicas (one replica per "
                    f"chip along the replica axis)")
            if self.G % shape[0]:
                raise ValueError(
                    f"group count {self.G} must divide evenly over "
                    f"{shape[0]} group shards")
        self.mesh = mesh
        self._mode = "sim" if mesh is None else "spmd-group"
        # SimCluster's put: every argument of a dispatch, of prewarm,
        # of the replay fetch and of a rebase goes to the device through
        # it, ``[G, R, ...]`` rows (and a burst's ``[K, G, R, ...]``
        # stacks) split ``P(group, replica)`` where there is a mesh
        self._put = make_put(self)
        # cache-key stand-in for the mesh: static device layout only —
        # deliberately independent of G, so clusters of ANY group
        # count on one mesh share compiled programs
        self._mesh_key = (None if mesh is None else
                          (mesh.devices.shape,
                           tuple(d.id for d in mesh.devices.flat)))
        # correctness observability (obs/audit.py): per-group digest
        # auditing keyed (group, term, index) — same mechanism as
        # SimCluster, widened by the group axis
        self._audit = audit
        if audit:
            from rdma_paxos_tpu.obs.audit import (
                AuditLedger, FlightRecorder)
            self.auditor = AuditLedger(self.R, self.G)
            self.flight = FlightRecorder(flight_capacity)
        else:
            self.auditor = None
            self.flight = None
        # device telemetry (obs/device.py) — the SimCluster mechanism
        # widened by the group axis: per-(group, replica) counter
        # vectors reduced at finish() and exported as
        # device_*{replica=,group=} series. On the mesh engine the
        # out_specs gather brings every chip's vector back into the
        # global [G, R, T_N] array, so per-shard counters survive the
        # shard_map (tests pin mesh ≡ vmap telemetry parity).
        self._telemetry = telemetry
        if telemetry:
            from rdma_paxos_tpu.obs import device as _device
            self.device_counters = _device.zeros(self.G, self.R)
        else:
            self.device_counters = None
        # cross-group transaction lane (txn/lane.py) — the SimCluster
        # mechanism widened by the group axis: per-group prepare
        # watches in the ABSOLUTE index domain (begin_step subtracts
        # each group's rebased_total), votes read back as the stacked
        # [G, R] matrix from the SAME dispatch that replicated the
        # prepares. txn=True compiles distinct serial step variants
        # (the audit=/telemetry= cache-key discipline); burst/scan
        # programs never carry the lane.
        self._txn = txn
        self._txn_watch = np.full((self.G,), -1, np.int64)
        self._txn_wterm = np.zeros((self.G,), np.int64)
        self.state = stack_group_states(cfg, self.G, self.R,
                                        self.group_size)
        if mesh is not None:
            # place the stacked state across the mesh up front so the
            # donated step never pays a layout change mid-serving
            self.state = jax.device_put(self.state,
                                        group_sharding(mesh))
        self._step_full = self._build_step(elections=True)
        # compile-count accounting: every shared-cache key this cluster
        # dispatches through (the single-compile guard's witness)
        self.programs_used: set = set()
        # device dispatch counters: protocol steps (the one-dispatch-
        # per-step claim shard_bench proves) and replay fetch sweeps
        self.dispatches = 0
        self.fetch_dispatches = 0
        self._replay_W = min(cfg.n_slots // 2,
                             max(4 * cfg.window_slots, 256))
        # SimCluster's fetch over [G, R]: the same few widths, the
        # same hook a traced benchmark run wraps
        self._replay_fetch = ReplayFetch(self._replay_W, 2)
        self._fetch_all = self._replay_fetch
        # ---- per-group host bookkeeping (mirrors SimCluster) ----
        G, R = self.G, self.R
        self.applied = np.zeros((G, R), np.int64)
        self.peer_mask = np.ones((G, R, R), np.int32)
        self.pending: List[List[list]] = [
            [[] for _ in range(R)] for _ in range(G)]
        # pipelined dispatch (begin_*/finish — same contract as
        # SimCluster): FIFO of in-flight tickets, staging-buffer pool,
        # host lock, dispatch-concurrency counters, dispatch clock
        self._tickets: collections.deque = collections.deque()
        self._staging = StagingPool()
        self._host_lock = threading.RLock()
        self.inflight_dispatches = 0
        self.max_inflight_dispatches = 0
        self._dispatch_clock = 0
        self.replayed: List[List[LazyReplayStream]] = [
            [LazyReplayStream() for _ in range(R)] for _ in range(G)]
        self.last: Optional[Dict[str, np.ndarray]] = None
        self.need_recovery: set = set()     # {(g, r)} force-pruned past
        self._wedged: set = set()           # {(g, r)} frozen apply
        self.rebases = np.zeros(G, np.int64)
        self.rebased_total = np.zeros(G, np.int64)
        self.rebase_stall_steps = np.zeros(G, np.int64)
        self.rebase_stalled = np.zeros(G, np.int64)
        self._prev_commit_max = np.zeros(G, np.int64)
        # optional per-group chaos link models (g -> LinkModel); purely
        # host-side input rewrites, like SimCluster.link_model
        self.link_models: Dict[int, object] = {}
        # read-path subsystem (runtime/reads.py): per-group leader
        # leases + queued read hub, observed/drained at the tail of
        # every finish() — same contract (and same attach()) as
        # SimCluster, widened by the group axis, so place_leaders
        # spreads lease-read serving across the R replicas
        self.leases = None
        self.reads = None
        # log-as-product streams hub (streams/__init__.py) — same
        # attach pattern and zero-new-STEP_CACHE-keys contract as
        # SimCluster, widened by the group axis (per-group cursors).
        self.streams = None
        # adaptive dispatch governor (runtime/governor.py) — observed
        # at the tail of every finish(), per-GROUP tier decisions over
        # the shared ladder (the dispatch uses the max rung; the
        # per-group rungs ride the trace events). Same attach pattern
        # and zero-new-STEP_CACHE-keys contract as SimCluster.
        self.governor = None
        # cross-group 2PC coordinator (txn/coordinator.py, attached
        # via txn.attach_coordinator): observed at the very tail of
        # every finish(), after the governor — same contract as
        # SimCluster. Host bookkeeping only.
        self.txn = None
        # elastic topology controller (topology/transition.py,
        # attached via topology.attach_topology): fed record
        # placements from the stamp loop (same outside-the-host-lock
        # contract as txn) and observed at the finish() tail, after
        # txn. Host bookkeeping only — zero device changes.
        self.topology = None
        # repair-held replicas barred from read serving ({(g, r)} —
        # see SimCluster.read_blocked)
        self.read_blocked: set = set()
        self.step_index = 0
        # host-side observability facade; NEVER read inside jitted code
        self.obs = None
        # optional obs.spans.StepPhaseProfiler — same hook points as
        # SimCluster (host_encode / device_dispatch / fenced sync /
        # quorum_wait / apply), plus per-group apply attribution
        # (step_phase_us{phase=apply, group=g}) recorded via self.obs
        self.profiler = None
        # store-ready framed blobs, per group per replica — byte-
        # identical to SimCluster's assembly (the G=1 parity contract);
        # only produced when a consumer opts in
        self.collect_frames = False
        self.frames: List[List[List[bytes]]] = [
            [[] for _ in range(R)] for _ in range(G)]
        # runtime lock sanitizer: the guarded-by declarations live in
        # runtime/sim.py (the fields are name-shared across both
        # engines) — under RP_SANITIZE=1 they become lock-ownership
        # assertions here too. No-op otherwise.
        from rdma_paxos_tpu.analysis import runtime_guard
        from rdma_paxos_tpu.runtime import sim as _sim_mod
        runtime_guard.maybe_guard(self, "_host_lock",
                                  _sim_mod.__file__, __file__)

    # ---------------- client-side API ----------------

    def submit(self, group: int, replica: int, payload: bytes,
               etype: EntryType = EntryType.SEND, conn: int = 1,
               req_id: int = 0) -> None:
        """Queue a client entry for the next step on ``replica`` of
        ``group`` (it only enters that group's log if the replica is
        its leader — proxy semantics, per group). Locked: a concurrent
        ``begin_*`` batch take swaps the pending list object, and an
        unlocked append to the old object would be silently lost."""
        with self._host_lock:
            self.pending[group][replica].append(
                (int(etype), conn, req_id, payload))

    def submit_many(self, group: int, replica: int,
                    entries: Sequence[Tuple[int, int, int, bytes]]
                    ) -> None:
        """Batched intake for one group's replica — see
        ``SimCluster.submit_many``."""
        with self._host_lock:
            self.pending[group][replica].extend(entries)

    def set_txn_watch(self, group: int, index: int, term: int) -> None:
        """Arm ``group``'s prepare watch: every subsequent serial step
        reports the group's per-replica vote for whether ABSOLUTE log
        index ``index`` is committed under ``term`` (txn=True clusters
        only). Sticky until cleared — the coordinator re-reads the
        ``[G, R]`` vote matrix each step while a prepare is out."""
        if not self._txn:
            raise RuntimeError("set_txn_watch requires txn=True")
        self._txn_watch[group] = int(index)
        self._txn_wterm[group] = int(term)

    def clear_txn_watch(self, group: Optional[int] = None) -> None:
        if group is None:
            self._txn_watch[:] = -1
            self._txn_wterm[:] = 0
        else:
            self._txn_watch[group] = -1
            self._txn_wterm[group] = 0

    def partition(self, group: int,
                  groups_of_replicas: Sequence[Sequence[int]]) -> None:
        """Partition ONE consensus group's replicas (other groups'
        connectivity is untouched — per-group fault domains)."""
        if self._fanout == "psum":
            raise ValueError(
                "partitions cannot be modeled with fanout='psum'; "
                "build the cluster with fanout='gather'")
        self.peer_mask[group, :, :] = 0
        for grp in groups_of_replicas:
            for i in grp:
                for j in grp:
                    self.peer_mask[group, i, j] = 1
        np.fill_diagonal(self.peer_mask[group], 1)

    def heal(self, group: Optional[int] = None) -> None:
        if group is None:
            self.peer_mask[:] = 1
        else:
            self.peer_mask[group, :, :] = 1

    def wedge_apply(self, group: int, r: int) -> None:
        self._wedged.add((group, r))

    def unwedge_apply(self, group: int, r: int) -> None:
        self._wedged.discard((group, r))

    # ---------------- stepping ----------------

    def _effective_mask(self) -> np.ndarray:
        """[G, R, R] hear-matrix: per-group base mask refined by that
        group's attached link model (host-side data only)."""
        if not self.link_models:
            return self.peer_mask
        mask = self.peer_mask.copy()
        for g, lm in self.link_models.items():
            mask[g] = lm.effective_mask(mask[g], self._dispatch_clock)
        return mask

    def _norm_timeouts(self, timeouts: TimeoutsLike) -> Dict[int, list]:
        if not timeouts:
            return {}
        if isinstance(timeouts, dict):
            return {int(g): list(rs) for g, rs in timeouts.items() if rs}
        out: Dict[int, list] = {}
        for g, r in timeouts:
            out.setdefault(int(g), []).append(int(r))
        return out

    def _step_bufs(self) -> dict:
        return self._staging.acquire(
            arg_layout(self.cfg, self.R, 1, self._txn), (self.G, self.R),
            fused=False)

    def _burst_bufs(self, K: int) -> dict:
        return self._staging.acquire(
            arg_layout(self.cfg, self.R, K), (self.G, self.R))

    # holds-lock: _host_lock
    def reserved_appends(self) -> np.ndarray:
        """[G, R] appends dispatched but not yet finished (pipelined
        capacity reservation — same rule as SimCluster)."""
        out = np.zeros((self.G, self.R), np.int64)
        for t in self._tickets:
            for g in range(self.G):
                for r in range(self.R):
                    out[g, r] += len(t.taken[g][r])
        return out

    def _build_step(self, *, elections: bool):
        """Fetch (or compile once into the SHARED runtime cache) the
        group-batched step. The cache key carries everything static
        that shapes the program — the engine mode and (for the mesh
        engine) the static device layout — and deliberately NOT the
        group count: the jitted callable is batch-size-polymorphic, so
        every homogeneous cluster shape shares one entry per variant
        (mesh clusters of any G on one mesh included)."""
        key = (self.cfg, self.R, self._mode, self._mesh_key,
               self._use_pallas, self._interpret, self._fanout,
               "group", elections) \
            + (("audit",) if self._audit else ()) \
            + (("telemetry",) if self._telemetry else ()) \
            + (("txn",) if self._txn else ())
        cached = STEP_CACHE.get(key)
        if cached is None:
            kw = dict(use_pallas=self._use_pallas,
                      interpret=self._interpret, fanout=self._fanout,
                      elections=elections, audit=self._audit,
                      telemetry=self._telemetry, txn=self._txn)
            if self.mesh is not None:
                cached = build_spmd_group_step(self.cfg, self.R,
                                               self.mesh, **kw)
            else:
                cached = build_sim_group_step(self.cfg, self.R, **kw)
            STEP_CACHE[key] = cached
        return cached, key

    def _burst_fn(self, K: int):
        key = (self.cfg, self.R, self._mode, self._mesh_key,
               self._use_pallas, self._interpret, self._fanout,
               "group-burst", K) \
            + (("audit",) if self._audit else ()) \
            + (("telemetry",) if self._telemetry else ())
        fn = STEP_CACHE.get(key)
        if fn is None:
            kw = dict(use_pallas=self._use_pallas,
                      interpret=self._interpret, fanout=self._fanout,
                      audit=self._audit, telemetry=self._telemetry)
            if self.mesh is not None:
                fn = build_spmd_group_burst(self.cfg, self.R,
                                            self.mesh, **kw)
            else:
                fn = build_sim_group_burst(self.cfg, self.R, **kw)
            STEP_CACHE[key] = fn
        return fn, key

    def _scan_slots(self, K: int) -> int:
        """K-sized staged replay width — see SimCluster._scan_slots."""
        return min(self._replay_W,
                   max(K * self.cfg.batch_slots,
                       self.cfg.window_slots))

    def _scan_fn(self, K: int):
        # distinct "group-scan"-marked cache keys: scan-off clusters'
        # key sets and programs are untouched (the audit=/telemetry=
        # guard discipline; pinned by test)
        key = (self.cfg, self.R, self._mode, self._mesh_key,
               self._use_pallas, self._interpret, self._fanout,
               "group-scan", K, self._scan_slots(K)) \
            + (("audit",) if self._audit else ()) \
            + (("telemetry",) if self._telemetry else ())
        fn = STEP_CACHE.get(key)
        if fn is None:
            kw = dict(replay_slots=self._scan_slots(K),
                      use_pallas=self._use_pallas,
                      interpret=self._interpret, fanout=self._fanout,
                      audit=self._audit, telemetry=self._telemetry)
            if self.mesh is not None:
                fn = build_spmd_group_scan(self.cfg, self.R,
                                           self.mesh, **kw)
            else:
                fn = build_sim_group_scan(self.cfg, self.R, **kw)
            STEP_CACHE[key] = fn
        return fn, key

    def prewarm(self, tiers: Optional[Sequence[int]] = None) -> None:
        """Compile every step variant (and burst tier) up front on
        copies of the live state. One compile covers ALL groups — the
        tiers are shared across groups by construction, and across
        clusters through the shared runtime cache."""
        cfg, G, R = self.cfg, self.G, self.R
        # through the dispatches' own put, at the dispatches' own
        # shapes: a committed, sharded argument and an uncommitted
        # one-chip argument are two executables of one ``jax.jit``
        # (see SimCluster.prewarm)
        def idle(lay):
            return self._put(lay.idle((G, R), self.peer_mask))
        packed = idle(arg_layout(cfg, R, 1, self._txn))
        for elections in (True, False):
            fn, _ = self._build_step(elections=elections)
            st = jax.tree.map(lambda x: x.copy(), self.state)
            fn(st, packed)
        for K in (tiers if tiers is not None else self.K_TIERS):
            fns = [self._burst_fn(K)]
            if self.scan:
                fns.append(self._scan_fn(K))
            packed = idle(arg_layout(cfg, R, K))
            for fn, _ in fns:
                st = jax.tree.map(lambda x: x.copy(), self.state)
                fn(st, packed)
        # and the replay fetch at every width (SimCluster.prewarm)
        self._replay_fetch.warm(self.state.log,
                                self._put(np.zeros((G, R), np.int32)))

    def begin_step(self, timeouts: TimeoutsLike = (),
                   take_batch: bool = True) -> StepTicket:
        """Encode + DISPATCH one protocol step for EVERY group in one
        device dispatch; returns the in-flight ticket immediately
        (pass to :meth:`finish`, FIFO — same pipelining contract as
        ``SimCluster.begin_step``). ``timeouts`` fires election timers
        per group: a dict ``{group: [replica, ...]}`` or an iterable
        of ``(group, replica)`` pairs."""
        cfg, G, R, B = self.cfg, self.G, self.R, self.cfg.batch_slots
        prof = self.profiler
        if prof is not None:
            prof.start("host_encode")
        tmo = self._norm_timeouts(timeouts)
        mask = self._effective_mask()
        if self._fanout == "psum" and not mask.all():
            raise ValueError(
                "psum fan-out requires full connectivity; use "
                "fanout='gather' to model partitions")
        bufs = self._step_bufs()
        count, qdepth = bufs["count"], bufs["qdepth"]
        count[:] = 0
        with held(prof, self._host_lock, "dispatch_lock_wait"):
            taken: List[List[list]] = [[[] for _ in range(R)]
                                       for _ in range(G)]
            for g in range(G):
                for r in range(R):
                    take = (self.pending[g][r][:B] if take_batch
                            else [])
                    if take:
                        self.pending[g][r] = self.pending[g][r][B:]
                    taken[g][r] = take
                    qdepth[g, r] = len(self.pending[g][r])
            bufs["applied"][:] = self.applied
        for g in range(G):
            for r in range(R):
                take = taken[g][r]
                if take:
                    pack_rows(bufs, (g, r), take, cfg.slot_bytes)
                    count[g, r] = len(take)
        tmo_arr = bufs["timeout"]
        tmo_arr[:] = 0
        for g, rs in tmo.items():
            for r in rs:
                tmo_arr[g, r] = 1
        bufs["peer_mask"][:] = mask
        if self._txn:
            # device watches compare log offsets: shift each armed
            # ABSOLUTE index by that group's i32 rollovers, then
            # broadcast across the replica axis
            bufs["txn_watch"][:] = np.where(
                self._txn_watch >= 0,
                self._txn_watch - self.rebased_total, -1)[:, None]
            bufs["txn_term"][:] = self._txn_wterm[:, None]
        if prof is not None:
            prof.start("input_transfer")
        packed = self._put(bufs["packed"])
        if prof is not None:
            prof.stop("input_transfer")
        # no timer fired in ANY group ⟹ Phase B is provably a no-op
        # for every group: dispatch the stable step (bit-identical)
        if self._stable_fast_path and not tmo:
            fn, key = self._build_step(elections=False)
        else:
            fn, key = self._step_full
        if prof is not None:
            prof.stop("host_encode")
            prof.start("device_dispatch")
        with held(prof, self._host_lock, "dispatch_lock_wait"):
            if prof is not None:
                prof.start("program_call")
            self.state, out = fn(self.state, packed)
            if prof is not None:
                prof.stop("program_call")
            ticket = StepTicket("step", out, taken, tmo, 1, bufs)
            self._tickets.append(ticket)
            self.inflight_dispatches += 1
            self.max_inflight_dispatches = max(
                self.max_inflight_dispatches, self.inflight_dispatches)
        if prof is not None:
            prof.stop("device_dispatch")
        self.dispatches += 1
        self.programs_used.add(key)
        self._dispatch_clock += 1
        return ticket

    def _tiers(self, max_k):
        """Fused tiers bounded at ``max_k`` (the shared
        ``runtime.sim.cap_tiers`` rule — one ladder, one fallback
        semantics, both engines; never a new STEP_CACHE key)."""
        return cap_tiers(self.K_TIERS, max_k)

    def begin_burst(self, max_k: Optional[int] = None) -> StepTicket:
        """Encode + DISPATCH up to ``max(K_TIERS)`` fused protocol
        steps for every group; returns the in-flight ticket. Capacity
        sizing subtracts appends reserved by other in-flight tickets
        (the pipelined clamp rule — see SimCluster.begin_burst).
        ``max_k`` caps the tier choice at a lower ladder rung (the
        governor's dial — ONE program still spans all groups, so the
        cap is the max over the per-group rungs)."""
        cfg, G, R, B = self.cfg, self.G, self.R, self.cfg.batch_slots
        assert self.last is not None, "burst requires a stepped cluster"
        prof = self.profiler
        if prof is not None:
            prof.start("host_encode")
        mask = self._effective_mask()
        if self._fanout == "psum" and not mask.all():
            raise ValueError(
                "psum fan-out requires full connectivity; use "
                "fanout='gather' to model partitions")
        tiers = self._tiers(max_k)
        take_n = np.zeros((G, R), np.int64)
        qdepth = np.zeros((G, R), np.int32)
        taken: List[List[list]] = [[[] for _ in range(R)]
                                   for _ in range(G)]
        with held(prof, self._host_lock, "dispatch_lock_wait"):
            reserved = self.reserved_appends()
            last = self.last
            for g in range(G):
                for r in range(R):
                    n = clamp_burst_take(
                        len(self.pending[g][r]),
                        int(last["end"][g, r]), int(last["head"][g, r]),
                        cfg.n_slots, tiers[-1] * B,
                        int(reserved[g, r]))
                    take_n[g, r] = n
                    taken[g][r] = self.pending[g][r][:n]
                    self.pending[g][r] = self.pending[g][r][n:]
                    qdepth[g, r] = len(self.pending[g][r])
            applied = self.applied.astype(np.int32)
        k_needed = max(1, int(-(-take_n.max() // B)))
        K = next(k for k in tiers if k >= k_needed)
        bufs = self._burst_bufs(K)
        count = bufs["count"]
        for g in range(G):
            for r in range(R):
                n = int(take_n[g, r])
                for k in range(-(-n // B) if n else 0):
                    pack_rows(bufs, (k, g, r),
                              taken[g][r][k * B:(k + 1) * B],
                              cfg.slot_bytes)
                for k in range(K):
                    count[k, g, r] = max(0, min(n - k * B, B))
        bufs["peer_mask"][:] = mask
        bufs["applied"][:] = applied
        bufs["qdepth"][:] = qdepth
        scan = self.scan
        fn, key = self._scan_fn(K) if scan else self._burst_fn(K)
        if prof is not None:
            prof.stop("host_encode")
            prof.start("device_dispatch")
            prof.start("input_transfer")
        packed = self._put(bufs["packed"])
        if prof is not None:
            prof.stop("input_transfer")
        with held(prof, self._host_lock, "dispatch_lock_wait"):
            if prof is not None:
                prof.start("program_call")
            self.state, outs = fn(self.state, packed)
            if prof is not None:
                prof.stop("program_call")
            ticket = StepTicket("scan" if scan else "burst", outs,
                                taken, {}, K, bufs,
                                applied0=applied if scan else None)
            if scan:
                self.scan_dispatches += 1
            self._tickets.append(ticket)
            self.inflight_dispatches += 1
            self.max_inflight_dispatches = max(
                self.max_inflight_dispatches, self.inflight_dispatches)
        if prof is not None:
            prof.stop("device_dispatch")
        self.dispatches += 1
        self.programs_used.add(key)
        self._dispatch_clock += K
        return ticket

    def finish(self, ticket: StepTicket) -> Dict[str, np.ndarray]:
        """Block on ``ticket``'s outputs and run every post-step host
        rule — tickets MUST finish in dispatch order (the same
        begin/finish contract as ``SimCluster``)."""
        assert self._tickets and self._tickets[0] is ticket, \
            "tickets must finish in dispatch (FIFO) order"
        # NOT popped here — see SimCluster.finish: the ticket stays in
        # _tickets (counted by reserved_appends) until ``last`` below
        # reflects its appends, and the deque only mutates under
        # _host_lock
        G, R, B = self.G, self.R, self.cfg.batch_slots
        prof = self.profiler
        out = ticket.out
        burst = ticket.kind == "burst"
        scan = ticket.kind == "scan"
        if prof is not None:
            prof.sync(out)              # fenced device_sync (opt-in)
            prof.start("quorum_wait")
        res = read_scalars(ticket)               # [G, R] per key
        # what is compiled only on request keeps a read of its own
        # (``readback_rest``): none in the default programs
        reads = 1
        if prof is not None:
            prof.start("readback_rest")
        if not (burst or scan) and self._txn and out.txn_vote is not None:
            # serial dispatches only: the txn lane never rides
            # burst/scan programs (their keys stay untouched)
            res["txn_vote"] = np.asarray(out.txn_vote)
            reads += 1
        if prof is not None:
            prof.stop("readback_rest")
            prof.count("readback_arrays_total", reads)
            # program steps whose full-ring rescan branch ran: its
            # predicate is reduced over the groups of one program, so
            # the column reads alike in all of them (sim.py's meaning)
            prof.count("cfg_rescans_total", int(res["cfg_rescanned"].max()))
            prof.stop("quorum_wait")
            prof.start("post_readback")
            for g in range(G):
                count_ring(prof, self.last, res, ticket.taken[g],
                           self.cfg.n_slots, g)
        if self._audit:
            if burst or scan:
                get = (out.__getitem__ if scan
                       else lambda k: getattr(out, "commit"
                                              if k == "audit_commit"
                                              else k))
                a_s = np.asarray(get("audit_start"))   # [K, G, R]
                a_d = np.asarray(get("audit_digest"))  # [K, G, R, W]
                a_t = np.asarray(get("audit_term"))    # [K, G, R, W]
                a_c = np.asarray(get("audit_commit"))  # [K, G, R]
                for k in range(a_s.shape[0]):
                    self._ingest_audit(a_s[k], a_d[k], a_t[k], a_c[k])
                res["audit_start"] = a_s[-1]
                res["audit_digest"] = a_d[-1]
                res["audit_term"] = a_t[-1]
            else:
                for k in ("audit_start", "audit_digest", "audit_term"):
                    res[k] = np.asarray(getattr(out, k))
                self._ingest_audit(res["audit_start"],
                                   res["audit_digest"],
                                   res["audit_term"], res["commit"])
        if self._telemetry:
            # per-(group, replica) device counters, reduced/accumulated
            # exactly like SimCluster (finish runs on the readback
            # thread under the pipelined driver); the mesh engine's
            # out_specs gather already collected every chip's vector
            # into the global [.., G, R, T_N] array
            from rdma_paxos_tpu.obs import device as _device
            tv = np.asarray(out["telemetry"] if scan
                            else out.telemetry, dtype=np.int64)
            res["telemetry"] = (_device.reduce_steps(tv)
                                if burst or scan else tv)
            _device.accumulate(self.device_counters, res["telemetry"])
            _device.ingest(self.obs, res["telemetry"])
        txn_notes = []
        appended = 0        # groups whose leader appended in this dispatch
        with self._host_lock:
            for g in range(G):
                for r in range(R):
                    take = ticket.taken[g][r]
                    if take and res["role"][g, r] == int(Role.LEADER):
                        acc_gr = int(res["accepted"][g, r])
                        appended += acc_gr > 0
                        self._stamp_appends(g, r, take, acc_gr, res)
                        if ((self.txn is not None
                             or self.topology is not None)
                                and acc_gr > 0):
                            txn_notes.append(
                                (g, r, take[:acc_gr],
                                 int(res["term"][g, r]),
                                 int(res["end"][g, r])
                                 + int(self.rebased_total[g])))
                        requeue_shortfall(self.pending[g][r], take,
                                          acc_gr)
        # coordinator/topology notification OUTSIDE _host_lock:
        # note_appends takes the coordinator (or controller) lock, and
        # client threads inside begin()/observe hold that lock while
        # submitting (which takes _host_lock) — invoking it from the
        # stamp loop would invert the coordinator -> cluster lock
        # order into an ABBA deadlock
        for note in txn_notes:
            if self.txn is not None:
                self.txn.note_appends(*note)
            if self.topology is not None:
                self.topology.note_appends(*note)
        if prof is not None:
            prof.count("group_appends_total", appended)
            prof.stop("post_readback")
            prof.start("apply")
        self._replay_committed(
            res, scan_rows=((out["replay_data"], out["replay_meta"],
                             ticket.applied0) if scan else None))
        if prof is not None:
            prof.stop("apply")
            prof.start("finish_tail")
        if self._audit:
            self._record_flight(res, ticket.taken, ticket.timeouts,
                                burst_k=ticket.K)
        with self._host_lock:
            self._tickets.popleft()     # retire: last now covers it
            self.inflight_dispatches -= 1
            # the per-group i32 rollover rewrites offsets host-side:
            # deferred while dispatches are in flight (see SimCluster)
            if not self._tickets:
                self._maybe_rebase(res)
            self.last = res
        self.step_index += ticket.K
        self._observe(res)
        # read path: per-group lease renew/revoke from the finished
        # step, then serve due queued reads (readback thread under
        # the pipelined driver — same contract as SimCluster)
        if self.leases is not None:
            self.leases.observe(self, res)
        if self.reads is not None:
            self.reads.drain(self)
        if self.streams is not None:
            self.streams.observe(self, res)
        if self.governor is not None:
            self.governor.observe(self, res)
        if self.txn is not None:
            self.txn.observe(self, res)
        if self.topology is not None:
            self.topology.observe(self, res)
        if burst or scan:
            self._staging.release(ticket.bufs, [
                ((k, g, r), min(B, len(t) - k * B))
                for g in range(G) for r in range(R)
                for t in (ticket.taken[g][r],)
                for k in range(-(-len(t) // B) if t else 0)])
        else:
            self._staging.release(ticket.bufs, [
                ((g, r), len(ticket.taken[g][r]))
                for g in range(G) for r in range(R)])
        if prof is not None:
            prof.stop("finish_tail")
        return res

    def drain(self) -> Optional[Dict[str, np.ndarray]]:
        """Finish every in-flight ticket in order; returns the final
        result (or None when nothing was in flight)."""
        res = None
        while self._tickets:
            res = self.finish(self._tickets[0])
        return res

    def step(self, timeouts: TimeoutsLike = ()) -> Dict[str, np.ndarray]:
        """One protocol step for EVERY group in one device dispatch.
        ``timeouts`` fires election timers per group: a dict
        ``{group: [replica, ...]}`` or an iterable of ``(group,
        replica)`` pairs. Returns ``[G, R]`` result arrays."""
        require_drained(self._tickets, "step")
        return self.finish(self.begin_step(timeouts))

    def step_burst(self, max_k: Optional[int] = None
                   ) -> Dict[str, np.ndarray]:
        """Drain every group's pending queues through up to
        ``max(K_TIERS)`` fused protocol steps in ONE device dispatch.
        Same contract as ``SimCluster.step_burst`` per group: no
        elections fire inside the burst; the caller must only burst
        while every trafficked group has a known leader. ``max_k``
        caps the tier (the governor's dial)."""
        require_drained(self._tickets, "step_burst")
        return self.finish(self.begin_burst(max_k=max_k))

    # ---------------- host apply / rebase ----------------

    def _replay_committed(self, res, scan_rows=None) -> None:
        """Per-group host apply loop — ALL groups' and replicas'
        windows ride ONE fetch dispatch per sweep (``ReplayFetch`` over
        ``[G, R]``). Same integrity rule as ``SimCluster``: a
        fetched entry whose stamped gidx disagrees with the expected
        apply index means the slot was recycled past this member —
        flag ``(g, r)`` for snapshot recovery and stop replaying.
        Frame assembly and the per-group apply-time histograms
        (``step_phase_us{phase=apply, group=g}``) ride the same decode
        pass. ``scan_rows``: the K-window scan tier's in-dispatch
        replay rows, consumed FIRST (see SimCluster) — a scan whose
        commit delta fits the staged window pays zero fetch
        dispatches."""
        import time as _time
        t_group: Dict[int, int] = {}
        if scan_rows is not None:
            wd_fut, wm_fut, applied0 = scan_rows
            staged = int(wm_fut.shape[-2])     # K-sized, <= replay_W
            wd_all = wm_all = None
            for g in range(self.G):
                for r in range(self.R):
                    if ((g, r) in self._wedged
                            or (g, r) in self.need_recovery):
                        continue
                    commit = int(res["commit"][g, r])
                    off = int(self.applied[g, r]) - int(applied0[g, r])
                    n = int(min(commit - self.applied[g, r],
                                staged - off))
                    if n <= 0 or off < 0:
                        continue
                    if wd_all is None:  # lazy: transfer only if used
                        wd_all = np.asarray(wd_fut)
                        wm_all = np.asarray(wm_fut)
                    t0 = _time.perf_counter_ns()
                    wd = wd_all[g, r, off:off + n]
                    wm = wm_all[g, r, off:off + n]
                    if int(wm[0, M_GIDX]) != self.applied[g, r]:
                        self.need_recovery.add((g, r))
                        continue
                    decode_window(wm, wd, n, self.replayed[g][r],
                                  self.frames[g][r],
                                  self.collect_frames,
                                  rebase=int(self.rebased_total[g]))
                    self.applied[g, r] += n
                    t_group[g] = (t_group.get(g, 0)
                                  + _time.perf_counter_ns() - t0)
        while True:
            todo = [(g, r) for g in range(self.G)
                    for r in range(self.R)
                    if (g, r) not in self._wedged
                    and (g, r) not in self.need_recovery
                    and self.applied[g, r] < int(res["commit"][g, r])]
            if not todo:
                break
            starts = self._put(self.applied.astype(np.int32))
            need = max(int(res["commit"][g, r] - self.applied[g, r])
                       for g, r in todo)
            prof = self.profiler
            if prof is not None:
                prof.start("replay_fetch")
            # bind under the host lock (donation hazard — see
            # SimCluster._replay_committed); block on results outside it
            with held(prof, self._host_lock, "fetch_lock_wait"):
                if prof is not None:
                    prof.start("fetch_enqueue")
                self._replay_fetch.need = need
                wd_fut, wm_fut = self._fetch_all(self.state.log, starts)
                if prof is not None:
                    prof.stop("fetch_enqueue")
            self.fetch_dispatches += 1
            if prof is not None:
                prof.start("fetch_read")
            # wm is read last: a wrapper over _fetch_all (the
            # benchmark's span) ends inside its conversion
            wd_all, wm_all = np.asarray(wd_fut), np.asarray(wm_fut)
            W = wm_all.shape[-2]        # the width the fetch chose
            if prof is not None:
                prof.stop("fetch_read")
                prof.stop("replay_fetch")
                prof.count("fetch_rows_total", W)
                prof.start("replay_decode")
            for g, r in todo:
                t0 = _time.perf_counter_ns()
                commit = int(res["commit"][g, r])
                n = int(min(commit - self.applied[g, r], W))
                wd, wm = wd_all[g, r], wm_all[g, r]
                if n > 0 and int(wm[0, M_GIDX]) != self.applied[g, r]:
                    self.need_recovery.add((g, r))
                    continue
                decode_window(wm, wd, n, self.replayed[g][r],
                              self.frames[g][r], self.collect_frames,
                              rebase=int(self.rebased_total[g]))
                self.applied[g, r] += n
                t_group[g] = (t_group.get(g, 0)
                              + _time.perf_counter_ns() - t0)
            if prof is not None:
                prof.stop("replay_decode")
        if (t_group and self.obs is not None
                and self.profiler is not None):
            from rdma_paxos_tpu.obs.metrics import LATENCY_BUCKETS_US
            for g, ns in sorted(t_group.items()):
                self.obs.metrics.observe(
                    "step_phase_us", ns / 1e3,
                    buckets=LATENCY_BUCKETS_US, phase="apply", group=g)

    def _rebase_stalled_step(self, g: int, res) -> None:
        self.rebase_stall_steps[g] += 1
        if self.rebase_stall_steps[g] < self.REBASE_STALL_STEPS:
            return
        self.rebase_stalled[g] += 1
        if self.obs is not None:
            from rdma_paxos_tpu.obs import trace as _trace
            self.obs.metrics.inc("rebase_stalled", group=g)
            if self.rebase_stall_steps[g] == self.REBASE_STALL_STEPS:
                heads = [int(res["head"][g, r]) for r in range(self.R)]
                self.obs.trace.record(
                    _trace.REBASE_STALLED, group=g,
                    end_max=int(res["end"][g].max()),
                    threshold=self.cfg.rebase_threshold,
                    min_head=min(heads), heads=heads,
                    steps=int(self.rebase_stall_steps[g]))

    # holds-lock: _host_lock
    def _maybe_rebase(self, res) -> None:
        """Per-group coordinated i32-offset rollover: each group whose
        max end crossed ``rebase_threshold`` drops every offset of ITS
        replicas by its own min head (rounded down to a multiple of
        n_slots) — other groups' offsets are untouched. All crossing
        groups shift in one elementwise pass. ``res`` is adjusted in
        place so callers observe post-rollover offsets."""
        ends = res["end"].max(axis=1)                       # [G]
        if ends.max() < self.cfg.rebase_threshold:
            return
        deltas = np.zeros(self.G, np.int64)
        for g in range(self.G):
            if ends[g] < self.cfg.rebase_threshold:
                continue
            heads = [int(res["head"][g, r]) for r in range(self.R)
                     if (g, r) not in self.need_recovery]
            delta = rebase_delta_of(heads, self.cfg.n_slots)
            if delta <= 0:
                self._rebase_stalled_step(g, res)
                continue
            deltas[g] = delta
        if not deltas.any():
            return
        self._apply_rebase(deltas)
        # rebound, not written in place: the packed row's views are
        # read-only (as SimCluster does). audit_start is an index too
        # (the ledger already ingested pre-rollover)
        for k in ("head", "apply", "commit", "end", "audit_start"):
            if k in res:
                res[k] = res[k] - deltas[:, None].astype(res[k].dtype)
        for g in np.nonzero(deltas)[0]:
            d = int(deltas[g])
            self.applied[g] -= d
            self.rebases[g] += 1
            self.rebased_total[g] += d
            self.rebase_stall_steps[g] = 0
            if self.obs is not None:
                from rdma_paxos_tpu.obs import trace as _trace
                self.obs.metrics.inc("rebases_total", group=int(g))
                self.obs.metrics.inc("rebased_entries_total", d,
                                     group=int(g))
                self.obs.trace.record(_trace.REBASE_APPLIED,
                                      group=int(g), delta=d,
                                      rebases=int(self.rebases[g]))

    # holds-lock: _host_lock
    def _apply_rebase(self, deltas: np.ndarray) -> None:
        """Elementwise per-group offset subtraction — the grouped form
        of ``consensus.snapshot.rebase_offsets`` (same invariants:
        delta <= that group's min head, multiple of n_slots). Called
        from ``_maybe_rebase`` under the host lock. That one program
        over the state where it lies: the deltas go out through the
        put, a group's in each of its rows, so that nothing moves
        between chips (eager operations would put their constants on
        one chip and spread them over the mesh)."""
        from rdma_paxos_tpu.consensus.snapshot import rebase_offsets
        d_gr = self._put(np.broadcast_to(
            deltas.astype(np.int32)[:, None], (self.G, self.R)))
        self.state = rebase_offsets(self.state, d_gr)
        if self.mesh is not None:
            # the program's outputs follow its inputs; re-place all the
            # same so the next donated dispatch can pay no reshard
            # (rebases are rare — deferred until the pipeline drains)
            self.state = jax.device_put(self.state,
                                        group_sharding(self.mesh))

    # ---------------- observability ----------------

    def redigest(self, group: int, replica: int, lo: int,
                 hi: int) -> int:
        """Range re-digest backfill for ONE group's replica (raw
        offsets of that group) — the per-group form of
        ``SimCluster.redigest``; other groups' state is untouched and
        their dispatches resume as soon as this drained serial pass
        returns. Shares the jitted redigest program (and its
        ``"redigest"``-marked cache key) with the single-group
        engine."""
        from rdma_paxos_tpu.runtime.sim import run_redigest
        return run_redigest(
            self, self.state.log.buf[group, replica], lo, hi,
            group=group, rebased_total=int(self.rebased_total[group]),
            replica=replica)

    def _ingest_audit(self, starts, digests, terms, commits) -> None:
        """Per-group digest ingestion: ledger keys are ``(group,
        absolute index)`` with each group's own ``rebased_total``
        correction (groups rebase independently). Runs before
        ``_maybe_rebase`` so raw offsets and corrections agree."""
        led = self.auditor
        led.obs = self.obs
        W = self.cfg.window_slots
        for g in range(self.G):
            reb = int(self.rebased_total[g])
            s_l = starts[g].tolist()
            c_l = commits[g].tolist()
            for r in range(self.R):
                start, commit = s_l[r], c_l[r]
                n = commit - start
                if n <= 0:
                    continue
                off = start - (commit - W)
                led.record_window(r, start + reb,
                                  digests[g, r, off:off + n],
                                  terms[g, r, off:off + n],
                                  commit + reb, group=g,
                                  step=self.step_index)

    def _record_flight(self, res, taken, tmo, burst_k: int = 1) -> None:
        """Same contract as ``SimCluster._record_flight``, widened by
        the group axis; arrays are copied (the sharded rebase mutates
        ``res`` rows in place after this runs)."""
        entry = dict(
            step=self.step_index, burst_k=burst_k,
            timeouts={int(g): [int(r) for r in rs]
                      for g, rs in dict(tmo).items()},
            rebased_total=self.rebased_total.copy(),
            inputs=taken,
            outputs={k: res[k].copy()
                     for k in ("term", "role", "leader_id", "head",
                               "apply", "commit", "end", "accepted")},
            applied=self.applied.copy(),
            digests=dict(start=res["audit_start"].copy(),
                         commit=res["commit"].copy(),
                         window=res["audit_digest"]))
        self.flight.record(entry)

    def _span_recorder(self):
        from rdma_paxos_tpu.obs.spans import active_recorder
        return active_recorder(self.obs)

    def _span_rep(self, g: int, r: int) -> int:
        """Namespaced span replica id: per-group frontiers must not
        collide in the recorder's per-replica heaps."""
        return g * self.R + r

    def _stamp_appends(self, g: int, r: int, take, acc: int,
                       res) -> None:
        """The accepted prefix of ``take`` landed at absolute indices
        ``[end-acc, end)`` on group ``g``'s leader ``r`` — stamp each
        sampled span with its ``(group, term, index)`` key."""
        spans = self._span_recorder()
        if spans is None or not spans.open_count or acc <= 0:
            return
        end_abs = int(res["end"][g, r]) + int(self.rebased_total[g])
        term = int(res["term"][g, r])
        replicas = [self._span_rep(g, rr) for rr in range(self.R)]
        for i, (_t, conn, req, _p) in enumerate(take[:acc]):
            spans.stamp_append(conn, req, term, end_abs - acc + i,
                               self._span_rep(g, r), replicas=replicas,
                               group=g)

    def _observe(self, res) -> None:
        """Per-group metric gauges/counters (``...{group=g}`` series)
        plus span commit/apply frontier advance. Host-side only."""
        spans = self._span_recorder()
        if spans is not None and spans.open_count:
            for g in range(self.G):
                rebased = int(self.rebased_total[g])
                for r in range(self.R):
                    rep = self._span_rep(g, r)
                    spans.commit_advance(
                        rep, int(res["commit"][g, r]) + rebased)
                    spans.apply_advance(
                        rep, int(self.applied[g, r]) + rebased)
        if self.obs is None:
            return
        m = self.obs.metrics
        for g in range(self.G):
            rebased = int(self.rebased_total[g])
            cmax = int(res["commit"][g].max()) + rebased
            m.set("shard_term", int(res["term"][g].max()), group=g)
            m.set("shard_commit", cmax, group=g)
            m.set("shard_apply",
                  int(self.applied[g].min()) + rebased, group=g)
            m.set("shard_leader", self.leader_hint(g), group=g)
            delta = cmax - int(self._prev_commit_max[g])
            if delta > 0:
                m.inc("shard_committed_entries_total", delta, group=g)
            self._prev_commit_max[g] = cmax

    def health(self) -> dict:
        """Aggregated sharded-cluster health: one snapshot per group
        (per-replica offsets/roles, rebase counters, recovery flags)
        plus the serialized ROUTER — the full routing table rides the
        health document so any observer reconstructs the exact
        key→group mapping without code."""
        from rdma_paxos_tpu.obs.health import make_snapshot
        res = self.last
        groups = []
        for g in range(self.G):
            fields = dict(
                group=g,
                leader=self.leader_hint(g),
                rebases=int(self.rebases[g]),
                rebased_total=int(self.rebased_total[g]),
                rebase_stalled=int(self.rebase_stalled[g]),
                need_recovery=sorted(r for (gg, r) in self.need_recovery
                                     if gg == g),
                applied=[int(a) for a in self.applied[g]],
            )
            if res is not None:
                for k in ("role", "term", "commit", "apply", "end",
                          "head"):
                    fields[k] = [int(v) for v in res[k][g]]
                fields["log_headroom"] = int(
                    self.cfg.rebase_threshold - res["end"][g].max())
            groups.append(make_snapshot(**fields))
        return dict(schema=1, n_groups=self.G, n_replicas=self.R,
                    dispatches=self.dispatches,
                    engine=self._mode,
                    mesh=(None if self.mesh is None else
                          dict(layout="%dx%d" % self.mesh.devices.shape,
                               group_shards=int(self.mesh.devices.shape[0]),
                               devices=[int(d.id)
                                        for d in self.mesh.devices.flat])),
                    router=self.router.to_dict(), groups=groups,
                    audit=(self.auditor.summary()
                           if self.auditor is not None else None),
                    leases=(self.leases.status()
                            if self.leases is not None else None),
                    topology=(self.topology.status()
                              if self.topology is not None else None))

    # ---------------- leadership ----------------

    def leader(self, group: int) -> int:
        """Group ``group``'s leader iff exactly one replica claims it
        (the strict ``SimCluster.leader`` rule), else -1."""
        assert self.last is not None
        ids = [r for r in range(self.R)
               if self.last["role"][group, r] == int(Role.LEADER)]
        return ids[0] if len(ids) == 1 else -1

    def leader_hint(self, group: int) -> int:
        """Highest-term self-claimed leader of ``group`` (the driver's
        failover view rule — terms are unique per leader), or -1."""
        if self.last is None:
            return -1
        claims = [(int(self.last["term"][group, r]), r)
                  for r in range(self.R)
                  if int(self.last["role"][group, r]) == int(Role.LEADER)]
        return max(claims)[1] if claims else -1

    def leaders(self) -> List[int]:
        return [self.leader_hint(g) for g in range(self.G)]

    def run_until_elected(self, group: int, candidate: int,
                          max_steps: int = 5) -> int:
        for _ in range(max_steps):
            res = self.step(timeouts={group: [candidate]})
            if res["role"][group, candidate] == int(Role.LEADER):
                return candidate
        raise AssertionError(
            f"election did not converge in group {group}")

    def place_leaders(self, policy: str = "round_robin",
                      max_steps: int = 12) -> List[int]:
        """Elect a leader in EVERY group, spread across the R replicas
        so the G leaderships don't pile onto replica 0.

        * ``round_robin`` — group g targets replica ``g % R``.
        * ``least_loaded`` — each group targets the replica currently
          holding the fewest leaderships (existing leaders counted
          first, then assignments made greedily in group order).

        Elections for different groups ride the SAME dispatches — the
        whole placement typically converges in one or two steps.
        Returns the per-group target list."""
        if policy == "round_robin":
            targets = [g % self.R for g in range(self.G)]
        elif policy == "least_loaded":
            load = [0] * self.R
            targets = [-1] * self.G
            for g in range(self.G):
                cur = self.leader_hint(g) if self.last is not None else -1
                if cur >= 0:
                    targets[g] = cur
                    load[cur] += 1
            for g in range(self.G):
                if targets[g] < 0:
                    t = int(np.argmin(load))
                    targets[g] = t
                    load[t] += 1
        else:
            raise ValueError(f"unknown placement policy: {policy!r}")
        for _ in range(max_steps):
            pending = {g: [targets[g]] for g in range(self.G)
                       if self.last is None
                       or self.leader(g) != targets[g]}
            if not pending:
                return targets
            self.step(timeouts=pending)
        undone = [g for g in range(self.G)
                  if self.leader(g) != targets[g]]
        if undone:
            raise AssertionError(
                f"leader placement did not converge for groups {undone}")
        return targets
