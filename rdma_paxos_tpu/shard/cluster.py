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

The host bookkeeping is ``runtime/sim.py``'s ``ClusterEngine``, the one
body ``SimCluster`` runs too, here over the lead shape ``(G, R)``. Two
execution engines behind it:

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
same ``replica_step`` core, the same host bookkeeping methods (the same
function objects, ``tests/test_shard.py``), the same shared compile
cache (``runtime/sim.py:STEP_CACHE``) — ``tests/test_shard.py`` pins
bit-identical G=1 ≡ ``SimCluster`` behavior on a recorded workload.

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

from typing import Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

from rdma_paxos_tpu.config import LogConfig
from rdma_paxos_tpu.consensus.log import EntryType
from rdma_paxos_tpu.consensus.state import Role
from rdma_paxos_tpu.parallel.mesh import (
    GROUP_AXIS, REPLICA_AXIS, build_mesh_2d, build_sim_group_burst,
    build_sim_group_scan, build_sim_group_step, build_spmd_group_burst,
    build_spmd_group_scan, build_spmd_group_step, group_sharding,
    stack_group_states)
from rdma_paxos_tpu.runtime.sim import ClusterEngine, run_redigest
from rdma_paxos_tpu.shard.router import KeyRouter

TimeoutsLike = Union[None, Dict[int, Sequence[int]],
                     Sequence[Tuple[int, int]]]


class ShardedCluster(ClusterEngine):
    """G-group × R-replica protocol simulation, one dispatch per step:
    the engine's front end over the lead shape ``(G, R)``, addressed
    by ``(group, replica)``. Every group is a scope of its own (its
    own ``peer_mask[g]``, rebase clock, txn watch, ledger keys
    ``(group, term, index)`` and ``...{group=g}`` series)."""

    _PROGRAMS = {
        "step": (("group",), build_sim_group_step, build_spmd_group_step),
        "burst": (("group-burst",), build_sim_group_burst,
                  build_spmd_group_burst),
        "scan": (("group-scan",), build_sim_group_scan,
                 build_spmd_group_scan),
    }

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
        R, G = int(n_replicas), int(n_groups)
        self.G = G
        self.router = router if router is not None else KeyRouter(G)
        # mesh engine: a 2-D (group, replica) device mesh — groups
        # sharded across chips, replica collectives named on the other
        # axis. None = the single-device vmap engine. A
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
            if shape[1] != R:
                raise ValueError(
                    f"mesh replica axis is {shape[1]} devices but the "
                    f"cluster has {R} replicas (one replica per "
                    f"chip along the replica axis)")
            if G % shape[0]:
                raise ValueError(
                    f"group count {G} must divide evenly over "
                    f"{shape[0]} group shards")
        # cache-key stand-in for the mesh: static device layout only —
        # deliberately independent of G, so clusters of ANY group
        # count on one mesh share compiled programs
        layout = (None if mesh is None else
                  (mesh.devices.shape,
                   tuple(d.id for d in mesh.devices.flat)))
        self._prev_commit_max = np.zeros(G, np.int64)
        # optional per-group chaos link models (g -> LinkModel); purely
        # host-side input rewrites, like SimCluster.link_model
        self.link_models: Dict[int, object] = {}
        super().__init__(
            cfg, (G, R),
            stack_group_states(cfg, G, R, group_size or R),
            mode="sim" if mesh is None else "spmd-group", mesh=mesh,
            key_mesh=(layout,),
            state_sharding=None if mesh is None else group_sharding(mesh),
            group_size=group_size, use_pallas=use_pallas,
            interpret=interpret, fanout=fanout,
            stable_fast_path=stable_fast_path, audit=audit,
            flight_capacity=flight_capacity, telemetry=telemetry,
            scan=scan, txn=txn)

    # ---------------- the engine's hooks ----------------

    def _norm_timeouts(self, timeouts: TimeoutsLike) -> tuple:
        """A dict ``{group: [replica, ...]}`` or an iterable of
        ``(group, replica)`` pairs."""
        kept: Dict[int, list] = {}
        if isinstance(timeouts, dict):
            kept = {int(g): [int(r) for r in rs]
                    for g, rs in timeouts.items() if rs}
        else:
            for g, r in timeouts or ():
                kept.setdefault(int(g), []).append(int(r))
        return kept, [(g, r) for g, rs in kept.items() for r in rs]

    def _link_models(self) -> dict:
        return self.link_models

    def _span_rep(self, g: int, r: int) -> int:
        """Namespaced span replica id: per-group frontiers must not
        collide in the recorder's per-replica heaps."""
        return g * self.R + r

    def _count_appends(self, prof, appended: int) -> None:
        prof.count("group_appends_total", appended)

    def _observe(self, res) -> None:
        """Per-group metric gauges/counters (``...{group=g}`` series).
        Host-side only."""
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

    # ---------------- client-side API ----------------

    def submit(self, group: int, replica: int, payload: bytes,
               etype: EntryType = EntryType.SEND, conn: int = 1,
               req_id: int = 0) -> None:
        """Queue a client entry for the next step on ``replica`` of
        ``group`` (it only enters that group's log if the replica is
        its leader — proxy semantics, per group)."""
        self._submit((group, replica),
                     [(int(etype), conn, req_id, payload)])

    def submit_many(self, group: int, replica: int,
                    entries: Sequence[Tuple[int, int, int, bytes]]
                    ) -> None:
        """Batched intake for one group's replica — see
        ``SimCluster.submit_many``."""
        self._submit((group, replica), entries)

    def set_txn_watch(self, group: int, index: int, term: int) -> None:
        """Arm ``group``'s prepare watch: every subsequent serial step
        reports the group's per-replica vote for whether ABSOLUTE log
        index ``index`` is committed under ``term`` (txn=True clusters
        only). Sticky until cleared — the coordinator re-reads the
        ``[G, R]`` vote matrix each step while a prepare is out."""
        self._arm_txn_watch((group,), index, term)

    def clear_txn_watch(self, group: Optional[int] = None) -> None:
        self._disarm_txn_watch(() if group is None else (group,))

    def partition(self, group: int,
                  groups_of_replicas: Sequence[Sequence[int]]) -> None:
        """Partition ONE consensus group's replicas (other groups'
        connectivity is untouched — per-group fault domains)."""
        if self._fanout == "psum":
            raise ValueError(
                "partitions cannot be modeled with fanout='psum'; "
                "build the cluster with fanout='gather'")
        self._partition((group,), groups_of_replicas)

    def heal(self, group: Optional[int] = None) -> None:
        self._heal(() if group is None else (group,))

    def wedge_apply(self, group: int, r: int) -> None:
        self._wedged.add((group, r))

    def unwedge_apply(self, group: int, r: int) -> None:
        self._wedged.discard((group, r))

    def redigest(self, group: int, replica: int, lo: int,
                 hi: int) -> int:
        """Range re-digest backfill for ONE group's replica (raw
        offsets of that group) — the per-group form of
        ``SimCluster.redigest``; other groups' state is untouched and
        their dispatches resume as soon as this drained serial pass
        returns. Shares the jitted redigest program (and its
        ``"redigest"``-marked cache key) with the single-group
        engine."""
        return run_redigest(
            self, self.state.log.buf[group, replica], lo, hi,
            group=group, rebased_total=int(self.rebased_total[group]),
            replica=replica)

    # ---------------- observability ----------------

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
        return self._leader((group,))

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
        return self._elect((group, candidate), {group: [candidate]},
                           max_steps)

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
