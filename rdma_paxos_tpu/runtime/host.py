"""Multi-host deployment — one consensus replica per host (per chip).

This is the TRUE distributed topology matching the reference's one-process-
per-machine deployment over InfiniBand (``benchmarks/run.sh`` starting N
replicas over ssh). The mapping of the reference's transports:

  IB multicast bootstrap (mcast JOIN,     jax.distributed.initialize —
  ud_exchange_rc_info 3-way handshake)    coordinator rendezvous + PJRT
                                          device exchange over DCN
  RC QP data plane (one-sided writes)     XLA collectives over ICI/DCN
                                          inside the jitted SPMD step
  per-peer MR/rkey exchange               handled by the runtime (no app-
                                          level analog needed)

Every host runs the SAME SPMD programs in the same order (multi-controller
JAX); per-host *values* differ — each host feeds its replica's StepInput
shard (client batches from its local proxy, its own election timer) and
reads back its replica's output shard. The collectives inside the step
synchronize the hosts, so the polling loops stay in lock-step naturally.

Usage (per host)::

    hd = HostReplicaDriver(cfg, process_id=i, num_processes=N,
                           coordinator="host0:9900")
    hd.step(batch=[...], timeout_fired=..., apply_done=...)  # every host
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from rdma_paxos_tpu.config import LogConfig
from rdma_paxos_tpu.consensus.log import EntryType
from rdma_paxos_tpu.consensus.step import (
    SCAN_KEYS, arg_layout, fetch_window, unpack_scalars)
from rdma_paxos_tpu.parallel.mesh import (
    REPLICA_AXIS, build_spmd_step, stack_states)


class HostReplicaDriver:
    """Per-host runtime for one replica of a multi-host group."""

    def __init__(self, cfg: LogConfig, *, process_id: int,
                 num_processes: int, coordinator: str,
                 group_size: Optional[int] = None,
                 initialize_distributed: bool = True,
                 fanout: str = "psum", audit: bool = False):
        if initialize_distributed:
            jax.distributed.initialize(
                coordinator_address=coordinator,
                num_processes=num_processes, process_id=process_id)
        self.cfg = cfg
        self.me = process_id
        self.R = num_processes
        devs = jax.devices()
        if len(devs) < self.R:
            raise RuntimeError(
                f"need {self.R} global devices, have {len(devs)}")
        self.mesh = Mesh(np.array(devs[:self.R]), (REPLICA_AXIS,))
        self._sharding = NamedSharding(self.mesh, P(REPLICA_AXIS))
        # real deployments run full-connectivity meshes: the O(W) psum
        # fan-out is sound there (see replica_step's fanout docstring)
        self._fanout = fanout
        # audit=True compiles the digest-chain variant (see
        # consensus/step.py): each host extracts ITS replica's digest
        # windows and records them locally; cross-host comparison
        # happens by merging the per-replica audit dumps
        # (python -m rdma_paxos_tpu.obs.audit report ...)
        self._audit = audit
        self._step = build_spmd_step(
            cfg, self.R, self.mesh, fanout=fanout, audit=audit,
            # same kernel as the benches: Pallas quorum scan on TPU
            use_pallas=jax.default_backend() == "tpu")
        # one jitted burst builder (lazily built): the scan length
        # follows the packed argument's rows, so jit specializes per K
        self._burst = None
        # the K-window scan tier (lazily built; RP_SCAN=1 daemons):
        # fused steps + consolidated readback + local replay window
        self._scan = None

        # HOST-LOCAL window fetch: reads THIS replica's log shard only —
        # a single-device program outside the SPMD step, so hosts may
        # call it independently (or not at all on idle iterations). The
        # collective window fetch this replaces forced every host into a
        # second lock-step program per iteration.
        from rdma_paxos_tpu.consensus.log import Log as _Log
        self._local_fetch = jax.jit(
            lambda buf, start: fetch_window(
                _Log(buf=buf, slot_words=cfg.slot_words), start,
                window_slots=cfg.window_slots))

        self.state = jax.device_put(stack_states(cfg, self.R, group_size
                                                 or self.R),
                                    self._sharding)
        self._local_dev = self.mesh.devices.flat[self.me]
        # persistent zero-copy staging rows for window encode, one a K
        # (a single step is K = 1): THIS replica's row of the
        # dispatch's ONE packed argument (``consensus/step.py``
        # ``arg_layout``) with views of it by field, allocated once and
        # repacked in place each iteration with only the
        # previously-dirty rows zeroed (per-step [B,...] allocation +
        # full memset was a measurable share of host_encode). Safe to
        # reuse because step()/step_burst() extract their outputs
        # before returning — the lock-step daemon never has a dispatch
        # in flight when the next iteration repacks.
        self._kstage: Dict[int, dict] = {}   # K -> staging row

    # ------------------------------------------------------------------

    def install_genesis(self, row: dict) -> None:
        """Install an identical pre-synchronized state row on EVERY
        replica of the world — the elastic-rebuild boot path (see
        ``consensus/snapshot.genesis_row``). Collective: every host calls
        this at the same point with the SAME row (all fetched it from the
        generation's donor)."""
        import dataclasses as _dc
        from rdma_paxos_tpu.consensus.log import pad_rows
        from rdma_paxos_tpu.consensus.state import ReplicaState

        def put(leaf: np.ndarray) -> jax.Array:
            shards = [jax.device_put(leaf[None], d)
                      for d in self.mesh.devices.flat
                      if d.process_index == jax.process_index()]
            return jax.make_array_from_single_device_arrays(
                (self.R,) + leaf.shape, self._sharding, shards)

        fields = {}
        for f in _dc.fields(ReplicaState):
            if f.name == "log":
                continue
            cur = getattr(self.state, f.name)
            fields[f.name] = put(np.asarray(row[f.name]).astype(cur.dtype))
        # ``log_buf`` travels as live columns: the pad goes back here
        fields["log"] = _dc.replace(self.state.log, buf=put(
            pad_rows(np.asarray(row["log_buf"], np.int32))))
        self.state = ReplicaState(**fields)

    def restore_hardstate(self, term: int, voted_term: int,
                          voted_for: int) -> None:
        """Install this host's persisted election state (HardState file)
        into its replica's state row — election safety across restarts: a
        recovered daemon must never re-grant a vote it already cast.
        Collective: every host calls this at the same point (pass zeros
        when it has no persisted state)."""
        g = self._global_from_local(
            np.array([term, voted_term, voted_for], np.int32))  # [R, 3]

        @jax.jit
        def upd(state, g):
            return dataclasses.replace(
                state,
                term=jnp.maximum(state.term, g[:, 0]),
                voted_for=jnp.where(g[:, 1] > state.voted_term,
                                    g[:, 2], state.voted_for),
                voted_term=jnp.maximum(state.voted_term, g[:, 1]),
            )
        self.state = upd(self.state, g)

    def _global_from_local(self, local: np.ndarray, fill=0,
                           other: Optional[np.ndarray] = None
                           ) -> jax.Array:
        """Build a [R, ...] global array where this host provides row
        ``me`` (other rows come from the other hosts). When several mesh
        devices are addressable by THIS process (single-process testing),
        the extra rows are ``other``, or filled with the NEUTRAL value
        ``fill`` (0 = no input)."""
        if other is None:
            other = np.full_like(local, fill)
        shards = []
        for d in self.mesh.devices.flat:
            if d.process_index != jax.process_index():
                continue
            row = local if d == self._local_dev else other
            shards.append(jax.device_put(row[None], d))
        return jax.make_array_from_single_device_arrays(
            (self.R,) + local.shape, self._sharding, shards)

    def _pack(self, K: int, batches, *, apply_done: int, gen: int,
              queue_depth: int, timeout_fired: bool = False,
              peer_mask: Optional[np.ndarray] = None) -> jax.Array:
        """The ``[R, rows, 128]`` packed argument of a K-step dispatch,
        this host providing its replica's row: up to K client batches
        (empty on followers) and the small words, written into the
        staging row's views. An extra row (single-process testing) is
        the idle one: no input, everyone heard (an all-zero mask would
        make those replicas deaf, not idle)."""
        st = self._kstage.get(K)
        if st is None:
            lay = arg_layout(self.cfg, self.R, K)
            packed = np.zeros(lay.shape(), np.int32)
            st = self._kstage[K] = dict(
                lay.views(packed), packed=packed, idle=lay.idle(),
                dirty=[0] * K)
        data, meta, dirty = st["data"], st["meta"], st["dirty"]
        for k, n in enumerate(dirty):
            if n:
                data[k, :n] = 0
                meta[k, :n] = 0
                dirty[k] = 0
        st["count"][:] = 0
        for k, batch in enumerate(list(batches)[:K]):
            dirty[k] = self._pack_batch(batch, data[k], meta[k], gen)
            st["count"][k] = dirty[k]
        st["peer_mask"][:] = 1 if peer_mask is None else peer_mask
        st["applied"][...] = apply_done
        st["qdepth"][...] = queue_depth
        st["timeout"][...] = int(timeout_fired)
        return self._global_from_local(st["packed"], other=st["idle"])

    def make_input(self, batch: Sequence[Tuple[int, int, int, bytes]] = (),
                   timeout_fired: bool = False,
                   apply_done: int = 0,
                   peer_mask: Optional[np.ndarray] = None,
                   gen: int = 0, queue_depth: int = 0) -> jax.Array:
        """A single step's packed argument."""
        if peer_mask is not None and self._fanout == "psum":
            # the psum fan-out is sound only under full connectivity: a
            # partition mask could leave two self-claimed leaders whose
            # windows SUM instead of being selected — reject loudly
            # rather than corrupt logs (use fanout="gather" to simulate
            # partitions)
            if not np.all(np.asarray(peer_mask) != 0):
                raise ValueError(
                    "psum fan-out requires an all-ones peer_mask; "
                    "build the driver with fanout='gather' to model "
                    "partitions")
        return self._pack(1, [batch], apply_done=apply_done, gen=gen,
                          queue_depth=queue_depth,
                          timeout_fired=timeout_fired,
                          peer_mask=peer_mask)

    def _pack_batch(self, batch, data: np.ndarray, meta: np.ndarray,
                    gen: int) -> int:
        """Fill one [B, ...] data/meta pair from (etype, conn, req,
        payload) rows — the single packing used by steps AND bursts,
        delegated to the shared vectorized host data plane
        (``hostpath.pack_window``: one payload join + one scatter per
        window; all three drivers pack through the one batched
        implementation). Returns the number of rows written (the
        caller's dirty count; rows are assumed pre-zeroed)."""
        from rdma_paxos_tpu.runtime.hostpath import pack_window
        du8 = data.view(np.uint8).reshape(data.shape[0], -1)
        return pack_window(du8, meta, list(batch)[:data.shape[0]],
                           self.cfg.slot_bytes, gen=gen)

    def step(self, **kw) -> Dict[str, np.ndarray]:
        """One collective protocol step; every host must call this in the
        same loop iteration. Returns THIS replica's scalar outputs."""
        inp = self.make_input(**kw)
        self.state, out = self._step(self.state, inp)
        res = self._local_scalars(out.scal, 0)
        for k in (("audit_start", "audit_digest", "audit_term")
                  if self._audit else ()):
            local = self._local_shard(getattr(out, k), 0)
            res[k] = np.asarray(local[0]) if local is not None else None
        return res

    def _local_shard(self, arr, axis: int):
        """THIS replica's shard of a global array sharded on ``axis``
        (None on a host that holds none). A 1-wide replica axis
        (single-host world) shards as slice(None), whose .start is
        None — that shard IS replica 0's."""
        sh = [s for s in arr.addressable_shards
              if (s.index[axis].start or 0) == self.me]
        return sh[0].data if sh else None

    def _local_scalars(self, scal, axis: int) -> Dict[str, np.ndarray]:
        """THIS replica's final-step scalars out of a dispatch's packed
        rows (``[R, N]`` of a step, ``[K, R, N]`` of a burst or scan,
        replica axis ``axis``): ONE local read, unpacked by the rule
        every engine shares (``accepted`` cumulative over a burst)."""
        local = self._local_shard(scal, axis)
        if local is None:
            return {k: None for k in SCAN_KEYS}
        rows = np.asarray(local)
        return unpack_scalars(rows.reshape(-1, rows.shape[-1])[-1])

    def _burst_fn(self):
        if self._burst is None:
            from rdma_paxos_tpu.parallel.mesh import build_spmd_burst
            self._burst = build_spmd_burst(
                self.cfg, self.R, self.mesh, fanout=self._fanout,
                audit=self._audit,
                use_pallas=jax.default_backend() == "tpu")
        return self._burst

    def step_burst(self, K: int,
                   batches: Sequence[Sequence[Tuple[int, int, int,
                                                    bytes]]] = (),
                   apply_done: int = 0, gen: int = 0,
                   queue_depth: int = 0) -> Dict[str, np.ndarray]:
        """K fused protocol steps in ONE collective dispatch. EVERY host
        must call this in the same iteration with the SAME K (derived
        from the gathered ``burst_hint`` — identical on all hosts under
        full connectivity; each distinct K is a separate compile, so
        drivers should stick to one K). ``batches``: up to K client
        batches for this host (empty on followers). ``queue_depth``:
        backlog REMAINING beyond this burst — it rides every burst
        step's gather so the final ``burst_hint`` sustains back-to-back
        bursts. No election timeouts fire inside a burst (each step
        carries the heartbeat). Returns this replica's final-step
        outputs plus ``accepted`` summed over the burst."""
        assert K > 0, K
        self.state, outs = self._burst_fn()(self.state, self._pack(
            K, batches, apply_done=apply_done, gen=gen,
            queue_depth=queue_depth))
        res = self._local_scalars(outs.scal, 1)
        if self._audit:
            # audit windows for EVERY fused step (not just the last) —
            # the daemon ingests them in order so the digest-chain
            # tiling holds through bursts; audit_commit carries the
            # matching per-step commit frontiers
            for k in ("audit_start", "audit_digest", "audit_term",
                      "commit"):
                local = self._local_shard(getattr(outs, k), 1)
                res["audit_commit" if k == "commit" else k] = (
                    np.asarray(local[:, 0]) if local is not None
                    else None)   # [K, ...]
        return res

    def _scan_fn(self):
        if self._scan is None:
            from rdma_paxos_tpu.parallel.mesh import build_spmd_scan
            self._scan = build_spmd_scan(
                self.cfg, self.R, self.mesh,
                replay_slots=self.cfg.window_slots,
                fanout=self._fanout, audit=self._audit,
                use_pallas=jax.default_backend() == "tpu")
        return self._scan

    def step_scan(self, K: int,
                  batches: Sequence[Sequence[Tuple[int, int, int,
                                                   bytes]]] = (),
                  apply_done: int = 0, gen: int = 0,
                  queue_depth: int = 0
                  ) -> Tuple[Dict[str, np.ndarray],
                             Tuple[np.ndarray, np.ndarray]]:
        """The K-window scan tier of :meth:`step_burst`: K fused
        protocol steps whose readback is ONE consolidated scalar
        matrix — plus this replica's replay window (``window_slots``
        committed rows from ``apply_done`` on, read from the POST-scan
        log inside the same dispatch), so the daemon's apply loop
        needs no per-window ``fetch_local_window`` dispatches for
        entries the scan already staged. Same collective-schedule
        contract as bursts: every host calls this in the same
        iteration with the same K. Returns ``(res, (wdata, wmeta))``;
        ``res`` matches :meth:`step_burst`'s (``accepted`` summed,
        audit windows per fused step when compiled)."""
        assert K > 0, K
        self.state, outs = self._scan_fn()(self.state, self._pack(
            K, batches, apply_done=apply_done, gen=gen,
            queue_depth=queue_depth))

        res = self._local_scalars(outs["scal"], 1)
        if self._audit and res["term"] is not None:
            for k in ("audit_start", "audit_digest", "audit_term",
                      "audit_commit"):
                loc = self._local_shard(outs[k], 1)     # [K, 1, ...]
                res[k] = (np.asarray(loc[:, 0]) if loc is not None
                          else None)
        wd = self._local_shard(outs["replay_data"], 0)   # [1, W, sw]
        wm = self._local_shard(outs["replay_meta"], 0)
        rows = (np.asarray(wd[0]) if wd is not None else None,
                np.asarray(wm[0]) if wm is not None else None)
        return res, rows

    def rebase(self, delta: int) -> None:
        """Apply the coordinated i32-offset rollover to this host's
        sharded state (see ``consensus/snapshot.rebase_offsets``). The
        program is purely elementwise — no collectives — so hosts may
        apply it independently once they agree on ``delta`` (the step's
        gathered ``rebase_delta`` output, identical on every host under
        full connectivity)."""
        from rdma_paxos_tpu.consensus.snapshot import rebase_offsets
        self.state = rebase_offsets(
            self.state, jnp.asarray(delta, jnp.int32))

    def export_local_row(self) -> dict:
        """THIS replica's full state row as host numpy (local shard reads
        only — no collective), keyed like ``snapshot.export_row``. The
        donor half of elastic world rebuild."""
        import dataclasses as _dc
        from rdma_paxos_tpu.consensus.state import ReplicaState

        from rdma_paxos_tpu.consensus.log import live_rows

        def local(arr):
            return np.asarray(self._local_shard(arr, 0)[0])

        log = self.state.log
        out = {"log_buf": np.asarray(live_rows(
            self._local_shard(log.buf, 0)[0], log.slot_words))}
        for f in _dc.fields(ReplicaState):
            if f.name != "log":
                out[f.name] = local(getattr(self.state, f.name))
        return out

    def fetch_local_window(self, start: int
                           ) -> Tuple[np.ndarray, np.ndarray]:
        """Read ``window_slots`` entries beginning at ``start`` from THIS
        replica's log. Host-local (no collective): call freely, on any
        host, only when needed."""
        wd, wm = self._local_fetch(
            self._local_shard(self.state.log.buf, 0)[0],
                                   jnp.asarray(start, jnp.int32))
        return np.asarray(wd), np.asarray(wm)
