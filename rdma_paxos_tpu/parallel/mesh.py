"""Replica mesh + the two execution modes of the protocol step.

The reference's distribution fabric is one RC QP pair per peer over
InfiniBand (``src/dare/dare_ibv_rc.c``). The TPU equivalent is a 1-D
``jax.sharding.Mesh`` over the ``replica`` axis — one consensus replica per
chip — with the protocol step compiled via ``shard_map`` so XLA lowers the
gathers onto ICI.

Because the step is written against an *axis name* (``lax.axis_index`` /
``lax.all_gather``), the identical protocol code also runs under
``jax.vmap(..., axis_name=REPLICA_AXIS)``: N replicas simulated on a single
chip (or CPU) with real collective semantics. That is the deterministic
multi-replica test harness the reference never had (SURVEY.md §4) and the
single-chip benchmarking mode.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from rdma_paxos_tpu.config import LogConfig
from rdma_paxos_tpu.consensus.log import extract_window
from rdma_paxos_tpu.consensus.state import ReplicaState, make_replica_state
from rdma_paxos_tpu.consensus.step import (
    GROUP_BATCH_AXIS, arg_layout, arg_layout_of, group_step, replica_step,
    scan_readback, vmap_groups, with_scalars)

REPLICA_AXIS = "replica"
GROUP_AXIS = "group"


def _shard_map(f, *, mesh: Mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking off (the step's
    outputs are per-replica by construction)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def make_replica_mesh(n_replicas: int,
                      devices: Optional[Sequence] = None) -> Mesh:
    """1-D mesh with one consensus replica per device."""
    devs = list(jax.devices() if devices is None else devices)[:n_replicas]
    if len(devs) < n_replicas:
        raise ValueError(
            f"need {n_replicas} devices, have {len(devs)}")
    import numpy as np
    return Mesh(np.array(devs), (REPLICA_AXIS,))


def build_mesh_2d(group_shards: int, replicas: int,
                  devices: Optional[Sequence] = None) -> Mesh:
    """2-D device mesh ``(group, replica)`` — the multi-chip layout of
    the sharded cluster. Groups are sharded across the ``group`` device
    axis (each device row owns ``G / group_shards`` whole groups);
    every replica-axis collective of the protocol step (the quorum
    gathers / psum fan-out) is named on the ``replica`` axis, so no
    collective ever crosses the group axis — the ICI traffic of G
    groups is G *independent* R-chip rings, exactly the fault/perf
    isolation the host layer assumes. Uses ``group_shards * replicas``
    devices."""
    need = int(group_shards) * int(replicas)
    devs = list(jax.devices() if devices is None else devices)
    if len(devs) < need:
        raise ValueError(
            f"need {need} devices for a {group_shards}x{replicas} "
            f"mesh, have {len(devs)}")
    import numpy as np
    return Mesh(np.array(devs[:need]).reshape(group_shards, replicas),
                (GROUP_AXIS, REPLICA_AXIS))


def axes_spec(mesh: Mesh, lead: int = 0) -> P:
    """``P`` of an array whose axes, after ``lead`` unsharded ones (a
    burst's K), are the mesh's: ``P("replica")`` on a replica mesh,
    ``P("group", "replica")`` on :func:`build_mesh_2d`'s. A mesh axis
    of ONE device shards nothing, and jit leaves it out of the specs of
    a program's outputs (``None``): it is left out here the same way,
    so that an argument put with this spec and the state a program
    handed back are ONE sharding, and one executable serves prewarm's
    placed state and the served one."""
    return P(*(None,) * lead,
             *(n if mesh.shape[n] > 1 else None for n in mesh.axis_names))


def group_sharding(mesh: Mesh):
    """The ``NamedSharding`` placing ``[group, replica, ...]`` state
    pytrees on a :func:`build_mesh_2d` mesh."""
    from jax.sharding import NamedSharding
    return NamedSharding(mesh, axes_spec(mesh))


def stack_states(cfg: LogConfig, n_replicas: int, group_size: int
                 ) -> ReplicaState:
    """Batched initial state: every leaf gains a leading replica axis."""
    one = make_replica_state(cfg, group_size, n_replicas)
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x, (n_replicas,) + x.shape), one)


def stack_group_states(cfg: LogConfig, n_groups: int, n_replicas: int,
                       group_size: int) -> ReplicaState:
    """Batched initial state for a sharded multi-group cluster: every
    leaf gains leading ``[group, replica]`` axes. All G groups start
    from the identical per-replica state — divergence comes only from
    per-group inputs (timeouts, batches, masks)."""
    one = stack_states(cfg, n_replicas, group_size)
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x, (n_groups,) + x.shape), one)


def _with_step_scalars(step, lay):
    """A batched single step over its ONE packed argument
    (``consensus/step.py`` ``arg_layout``, K = 1), whose output carries
    its packed readback row. ``wraps`` keeps the step's name, which
    names the compiled program (``jit_replica_step``) in device
    traces."""
    @functools.wraps(step)
    def stepped(state_b, packed):
        st, out = step(state_b, lay.step_input(lay.split(packed), 0))
        return st, with_scalars(out, out.accepted, st)
    return stepped


def _fused_steps(step, cfg, n_replicas, state, packed, readback):
    """The K steps of a fused program (burst or scan tier), K read off
    the packed argument's rows: a ``lax.scan`` of ``step`` over the
    step index, each step's batch read in place out of the packed rows
    (``ArgLayout.step_input``). The host's apply cursors and remaining
    backlog are the same in every step, no timer fires, ``accepted``
    is carried cumulative; ``readback(out, acc, st)`` is what a step
    hands back. Returns ``(state, stacked readbacks, parts)``."""
    lay = arg_layout_of(cfg, n_replicas, packed.shape[-2])
    parts = lay.split(packed)
    # created in-trace, NOT closure-captured: a captured jnp array is
    # embedded in the lowered module as a literal
    zeros = jnp.zeros_like(parts["applied"])

    def body(carry, k):
        st, acc = carry
        st, out = step(st, lay.step_input(parts, k, timeout_fired=zeros))
        acc = acc + out.accepted
        return (st, acc), readback(out, acc, st)
    (st, _acc), ys = lax.scan(body, (state, zeros),
                              jnp.arange(lay.K, dtype=jnp.int32))
    return st, ys, parts


def _squeeze(tree, axis=0):
    return jax.tree.map(lambda x: jnp.squeeze(x, axis), tree)


def _unsqueeze(tree, axis=0):
    return jax.tree.map(lambda x: jnp.expand_dims(x, axis), tree)


def _replica_core(cfg, n_replicas, **kw):
    return functools.partial(replica_step, cfg=cfg, n_replicas=n_replicas,
                             axis_name=REPLICA_AXIS, **kw)


def _jit(fn, donate):
    return jax.jit(fn, donate_argnums=(0,) if donate else ())


def build_spmd_step(cfg: LogConfig, n_replicas: int, mesh: Mesh, *,
                    use_pallas: bool = False, interpret: bool = False,
                    donate: bool = True, fanout: str = "gather",
                    elections: bool = True, audit: bool = False,
                    telemetry: bool = False, txn: bool = False):
    """Compile the protocol step over a real device mesh.

    Takes the *batched* state (leading ``replica`` axis, sharded one
    row per device) and the step's ONE packed argument ``[R, rows, 128]``
    (``arg_layout(cfg, R, 1, txn)``), sharded the same way. State
    buffers are donated so the log arrays update in-place on device
    across steps — the analog of the reference's log living pinned in
    registered MRs (``rc_memory_reg``, ``dare_ibv_rc.c:240-276``).
    """
    core = _replica_core(
        cfg, n_replicas, use_pallas=use_pallas, interpret=interpret,
        fanout=fanout, elections=elections, audit=audit,
        telemetry=telemetry, txn=txn)
    lay = arg_layout(cfg, n_replicas, 1, txn)

    def per_device(state_b, packed_b):
        st, out = core(_squeeze(state_b),
                       lay.step_input(lay.split(packed_b[0]), 0))
        return (_unsqueeze(st),
                _unsqueeze(with_scalars(out, out.accepted, st)))

    mapped = _shard_map(
        per_device, mesh=mesh,
        in_specs=(P(REPLICA_AXIS), P(REPLICA_AXIS)),
        out_specs=(P(REPLICA_AXIS), P(REPLICA_AXIS)))
    return _jit(mapped, donate)


def build_sim_burst(cfg: LogConfig, n_replicas: int, *,
                    use_pallas: bool = False, interpret: bool = False,
                    donate: bool = True, fanout: str = "gather",
                    audit: bool = False,
                    telemetry: bool = False):
    """K protocol steps fused into ONE dispatch (``lax.scan``) over the
    vmapped axis — the multi-step driver mode that amortizes host dispatch
    overhead when the submit queue is deep (the analog of the reference's
    busy commit loop staying on the NIC for many iterations per poll,
    ``rc_write_remote_logs`` ``dare_ibv_rc.c:1870-1948``).

    No elections fire inside a burst (timeouts forced 0; every scan step
    carries the leader heartbeat), so the burst compiles the STABLE step
    (``elections=False`` — Phase B could only ever be a no-op; statically
    removing it drops one collective per scan step). The host apply
    cursors are frozen across the burst (the host cannot replay
    mid-burst), so pruning advances at most to the pre-burst applied
    offsets; the caller's capacity sizing must fit the whole burst in
    the pre-burst free space. The burst's host inputs are ONE packed
    array ``[R, rows, 128]`` (``arg_layout(cfg, R, K)``: K steps' ``data
    [K, B, sw]``, ``meta [K, B, MW]`` and ``count [K]`` a replica, its
    ``peer_mask`` row, ``applied`` = the HOST's true apply cursor —
    echoing ``st.commit`` would let pressure-gated (and forced) pruning
    recycle slots the host has not replayed yet — and ``qdepth`` = the
    host backlog REMAINING beyond this burst, so the final step's
    gathered burst_hint keeps bursts back-to-back under sustained
    load); K is read off its rows. Returns the final state plus the
    per-step stacked outputs, whose ``scal`` (``[K, R, len(SCAN_KEYS) +
    R]``, ``accepted`` cumulative in-program) is the ONE array the
    host reads: row ``[-1]`` is the burst's result."""
    core = _replica_core(
        cfg, n_replicas, use_pallas=use_pallas, interpret=interpret,
        fanout=fanout, elections=False, audit=audit, telemetry=telemetry)
    vstep = jax.vmap(core, in_axes=(0, 0), axis_name=REPLICA_AXIS)

    def burst(state_b, packed):
        st, outs, _parts = _fused_steps(vstep, cfg, n_replicas, state_b,
                                        packed, with_scalars)
        return st, outs
    return _jit(burst, donate)


def _scan_tier(step, fetch, cfg, n_replicas, audit, telemetry):
    """``(state, packed) -> (state, readback dict)`` of the K-window
    scan tier: :func:`_fused_steps` handing back the consolidated
    minimal readback, and ``fetch(log, applied)``'s replay rows out of
    the post-scan log."""
    readback = functools.partial(scan_readback, audit=audit,
                                 telemetry=telemetry)

    def scan(state, packed):
        st, ys, parts = _fused_steps(step, cfg, n_replicas, state,
                                     packed, readback)
        ys["replay_data"], ys["replay_meta"] = fetch(st.log,
                                                     parts["applied"])
        return st, ys
    return scan


def build_sim_scan(cfg: LogConfig, n_replicas: int, *,
                   replay_slots: int,
                   use_pallas: bool = False, interpret: bool = False,
                   donate: bool = True, fanout: str = "gather",
                   audit: bool = False, telemetry: bool = False):
    """The device-resident K-window scan tier: K fused protocol steps
    (the :func:`build_sim_burst` ``lax.scan``, over the same ONE packed
    argument) returning ONE consolidated minimal readback instead of
    the full per-step output stacks — only what the host rules consume:

    * ``scal`` ``[K, R, len(SCAN_KEYS) + R]`` i32 — the per-step
      packed rows (``accepted`` cumulative; config view and the
      ``peer_acked`` row included; the host reads row ``[-1]``),
    * ``replay_data``/``replay_meta`` — ``replay_slots`` committed
      rows per replica starting at the host's PRE-scan apply cursors,
      extracted from the post-scan log INSIDE the same dispatch, so
      the host's replay sweep needs no separate fetch dispatch,
    * per-step audit windows / telemetry vectors, only when those
      variants are compiled (the ``audit=``/``telemetry=`` guard
      discipline — default programs carry neither).

    The protocol computation is exactly the burst's (stable step,
    same inputs, same donation), so scan outputs are bit-identical to
    K serial steps — pinned by ``tests/test_scan.py``. Engines cache
    the compiled fn under distinct ``"scan"``-marked STEP_CACHE keys:
    scan-off clusters' key sets and programs are untouched."""
    core = _replica_core(
        cfg, n_replicas, use_pallas=use_pallas, interpret=interpret,
        fanout=fanout, elections=False, audit=audit, telemetry=telemetry)
    vstep = jax.vmap(core, in_axes=(0, 0), axis_name=REPLICA_AXIS)
    vfetch = jax.vmap(lambda log, s: extract_window(
        log, s, replay_slots))
    return _jit(_scan_tier(vstep, vfetch, cfg, n_replicas, audit,
                           telemetry), donate)


def build_sim_group_scan(cfg: LogConfig, n_replicas: int, *,
                         replay_slots: int,
                         use_pallas: bool = False,
                         interpret: bool = False,
                         donate: bool = True, fanout: str = "gather",
                         audit: bool = False,
                         telemetry: bool = False):
    """:func:`build_sim_scan` with a leading ``group`` batch axis —
    the sharded engine's K-window scan tier (the packed argument
    shaped like :func:`build_sim_group_burst`'s; readback dict axes
    gain ``G``)."""
    gstep = group_step(cfg=cfg, n_replicas=n_replicas,
                       axis_name=REPLICA_AXIS, use_pallas=use_pallas,
                       interpret=interpret, fanout=fanout,
                       elections=False, audit=audit,
                       telemetry=telemetry)
    vfetch = jax.vmap(jax.vmap(lambda log, s: extract_window(
        log, s, replay_slots)))
    return _jit(_scan_tier(gstep, vfetch, cfg, n_replicas, audit,
                           telemetry), donate)


def _scan_out_spec(spec_k, spec_rows, audit, telemetry):
    out_spec = dict(scal=spec_k, replay_data=spec_rows,
                    replay_meta=spec_rows)
    if audit:
        out_spec.update(audit_start=spec_k, audit_digest=spec_k,
                        audit_term=spec_k, audit_commit=spec_k)
    if telemetry:
        out_spec["telemetry"] = spec_k
    return out_spec


def build_spmd_group_scan(cfg: LogConfig, n_replicas: int, mesh: Mesh,
                          *, replay_slots: int,
                          use_pallas: bool = False,
                          interpret: bool = False,
                          donate: bool = True, fanout: str = "gather",
                          audit: bool = False,
                          telemetry: bool = False):
    """:func:`build_sim_group_scan` over the 2-D ``(group, replica)``
    mesh: the K-window scan (fused steps + consolidated readback +
    in-dispatch replay-window extraction) compiled via ``shard_map``.
    Each device extracts its own replicas' replay rows locally; the
    out_specs gather assembles the global ``[G, R, ...]`` arrays the
    host bookkeeping expects — same host code as the vmap engine."""
    core = _replica_core(
        cfg, n_replicas, use_pallas=use_pallas, interpret=interpret,
        fanout=fanout, elections=False, audit=audit, telemetry=telemetry,
        group_batch_axis=GROUP_BATCH_AXIS)
    scan = _scan_tier(
        vmap_groups(core),                      # the device's own groups
        jax.vmap(lambda log, s: extract_window(log, s, replay_slots)),
        cfg, n_replicas, audit, telemetry)

    def per_device(state_b, packed_b):          # [Gl, 1, ...]
        st, ys = scan(_squeeze(state_b, 1), packed_b[:, 0])
        rows = ("replay_data", "replay_meta")
        return (_unsqueeze(st, 1),              # ys: [K, Gl, 1, ...]
                {k: _unsqueeze(v, 1 if k in rows else 2)
                 for k, v in ys.items()})

    spec = P(GROUP_AXIS, REPLICA_AXIS)
    mapped = _shard_map(
        per_device, mesh=mesh, in_specs=(spec, spec),
        out_specs=(spec, _scan_out_spec(
            P(None, GROUP_AXIS, REPLICA_AXIS), spec, audit, telemetry)))
    return _jit(mapped, donate)


def build_spmd_scan(cfg: LogConfig, n_replicas: int, mesh: Mesh, *,
                    replay_slots: int,
                    use_pallas: bool = False, interpret: bool = False,
                    donate: bool = True, fanout: str = "psum",
                    audit: bool = False, telemetry: bool = False):
    """:func:`build_sim_scan` over a real 1-D replica mesh — the
    multi-host daemon's K-window scan tier: K fused steps + the
    consolidated scalar matrix + each host's OWN replay window
    extracted from its local log shard inside the one collective
    dispatch (the per-iteration ``fetch_local_window`` dispatches of
    the lock-step loop disappear)."""
    core = _replica_core(
        cfg, n_replicas, use_pallas=use_pallas, interpret=interpret,
        fanout=fanout, elections=False, audit=audit, telemetry=telemetry)
    scan = _scan_tier(
        core, lambda log, s: extract_window(log, s, replay_slots),
        cfg, n_replicas, audit, telemetry)

    def per_device(state_b, packed_b):          # [1, ...]
        st, ys = scan(_squeeze(state_b), packed_b[0])
        rows = ("replay_data", "replay_meta")
        return (_unsqueeze(st),                 # ys: [K, 1, ...]
                {k: _unsqueeze(v, 0 if k in rows else 1)
                 for k, v in ys.items()})

    spec = P(REPLICA_AXIS)
    mapped = _shard_map(
        per_device, mesh=mesh, in_specs=(spec, spec),
        out_specs=(spec, _scan_out_spec(P(None, REPLICA_AXIS), spec,
                                        audit, telemetry)))
    return _jit(mapped, donate)


def build_spmd_burst(cfg: LogConfig, n_replicas: int, mesh: Mesh, *,
                     use_pallas: bool = False, interpret: bool = False,
                     donate: bool = True, fanout: str = "gather",
                     audit: bool = False,
                     telemetry: bool = False):
    """:func:`build_sim_burst` over a real device mesh (``shard_map`` with
    the K-step scan inside the per-device program, each device handed
    its replica's row of the ONE packed argument); the same stacked
    outputs, ``scal`` the one array the host reads."""
    core = _replica_core(
        cfg, n_replicas, use_pallas=use_pallas, interpret=interpret,
        fanout=fanout, elections=False, audit=audit, telemetry=telemetry)

    def per_device(state_b, packed_b):          # [1, ...]
        st, outs, _parts = _fused_steps(
            core, cfg, n_replicas, _squeeze(state_b), packed_b[0],
            with_scalars)
        return _unsqueeze(st), _unsqueeze(outs, 1)      # [K, 1, ...]

    mapped = _shard_map(
        per_device, mesh=mesh,
        in_specs=(P(REPLICA_AXIS), P(REPLICA_AXIS)),
        out_specs=(P(REPLICA_AXIS), P(None, REPLICA_AXIS)))
    return _jit(mapped, donate)


def build_sim_group_step(cfg: LogConfig, n_replicas: int, *,
                         use_pallas: bool = False, interpret: bool = False,
                         donate: bool = True, fanout: str = "gather",
                         elections: bool = True, audit: bool = False,
                         telemetry: bool = False, txn: bool = False):
    """Compile the G-group × R-replica protocol step as ONE program on
    one device (:func:`rdma_paxos_tpu.consensus.step.group_step` under
    ``jit``) over the ONE packed argument ``[G, R, rows, 128]``. The group
    axis is a batch axis — groups are independent;
    only the replica axis carries protocol collectives (the group axis
    is named for one scalar, the rescan's gate: ``group_step``) — so
    one dispatch steps every group (the sharded-cluster hot path)."""
    gstep = group_step(cfg=cfg, n_replicas=n_replicas,
                       axis_name=REPLICA_AXIS, use_pallas=use_pallas,
                       interpret=interpret, fanout=fanout,
                       elections=elections, audit=audit,
                       telemetry=telemetry, txn=txn)
    return _jit(_with_step_scalars(
        gstep, arg_layout(cfg, n_replicas, 1, txn)), donate)


def build_sim_group_burst(cfg: LogConfig, n_replicas: int, *,
                          use_pallas: bool = False,
                          interpret: bool = False,
                          donate: bool = True, fanout: str = "gather",
                          audit: bool = False,
                          telemetry: bool = False):
    """:func:`build_sim_burst` with a leading ``group`` batch axis: K
    fused protocol steps over ALL G groups in ONE dispatch
    (``lax.scan`` of the group-batched stable step). Same contract as
    the single-group burst — no elections inside the burst, host apply
    cursors frozen across it, capacity sized by the caller — applied
    per group. The packed argument is ``[G, R, rows, 128]``, a (group,
    replica) pair's row laid out as :func:`build_sim_burst`'s."""
    gstep = group_step(cfg=cfg, n_replicas=n_replicas,
                       axis_name=REPLICA_AXIS, use_pallas=use_pallas,
                       interpret=interpret, fanout=fanout,
                       elections=False, audit=audit,
                       telemetry=telemetry)

    def burst(state_gb, packed):
        st, outs, _parts = _fused_steps(gstep, cfg, n_replicas, state_gb,
                                        packed, with_scalars)
        return st, outs
    return _jit(burst, donate)


def build_spmd_group_step(cfg: LogConfig, n_replicas: int, mesh: Mesh,
                          *, use_pallas: bool = False,
                          interpret: bool = False, donate: bool = True,
                          fanout: str = "gather",
                          elections: bool = True, audit: bool = False,
                          telemetry: bool = False, txn: bool = False):
    """:func:`build_sim_group_step` over a REAL 2-D ``(group,
    replica)`` device mesh (:func:`build_mesh_2d`): G groups × R
    replicas advanced by ONE ``shard_map``-compiled dispatch spanning
    ``group_shards * R`` chips.

    Axis layout: the global ``[G, R, ...]`` state and the packed
    argument ``[G, R, rows, 128]`` are sharded
    ``P(group, replica)`` — each device holds ``G / group_shards``
    whole group rows of exactly one replica column. Inside the
    per-device program the replica axis (local size 1) is squeezed and
    the local group rows ride a ``vmap`` whose axis name is LOCAL
    (``vmap_groups``: the rescan's gate is reduced over a device's own
    groups, on the chip), so every collective in :func:`replica_step`
    that leaves the chip binds the ``replica`` MESH axis only: quorum
    traffic crosses the R chips of one replica ring, never the
    ``group`` mesh axis. The compiled program is polymorphic in the
    local group count, so the cache key carries the mesh — not G
    (``tests/test_mesh.py`` pins the single-compile property)."""
    core = _replica_core(
        cfg, n_replicas, use_pallas=use_pallas, interpret=interpret,
        fanout=fanout, elections=elections, audit=audit,
        telemetry=telemetry, txn=txn, group_batch_axis=GROUP_BATCH_AXIS)
    vcore = vmap_groups(core)                   # the device's own groups
    lay = arg_layout(cfg, n_replicas, 1, txn)

    def per_device(state_b, packed_b):          # [Gl, 1, ...]
        st, out = vcore(_squeeze(state_b, 1),
                        lay.step_input(lay.split(packed_b[:, 0]), 0))
        return (_unsqueeze(st, 1),
                _unsqueeze(with_scalars(out, out.accepted, st), 1))

    spec = P(GROUP_AXIS, REPLICA_AXIS)
    mapped = _shard_map(per_device, mesh=mesh, in_specs=(spec, spec),
                        out_specs=(spec, spec))
    return _jit(mapped, donate)


def build_spmd_group_burst(cfg: LogConfig, n_replicas: int, mesh: Mesh,
                           *, use_pallas: bool = False,
                           interpret: bool = False,
                           donate: bool = True, fanout: str = "gather",
                           audit: bool = False,
                           telemetry: bool = False):
    """:func:`build_sim_group_burst` over the 2-D ``(group, replica)``
    mesh: K fused protocol steps × ALL G groups in ONE multi-chip
    dispatch (``lax.scan`` of the group-vmapped stable step inside the
    per-device program). Same contract as the single-device group
    burst — no elections inside, host apply cursors frozen, capacity
    sized by the caller — applied per group. The packed argument is
    :func:`build_sim_group_burst`'s, sharded ``P(group, replica)``:
    a device is handed its replica's rows whole."""
    core = _replica_core(
        cfg, n_replicas, use_pallas=use_pallas, interpret=interpret,
        fanout=fanout, elections=False, audit=audit, telemetry=telemetry,
        group_batch_axis=GROUP_BATCH_AXIS)
    vcore = vmap_groups(core)                   # the device's own groups

    def per_device(state_b, packed_b):          # [Gl, 1, ...]
        st, outs, _parts = _fused_steps(
            vcore, cfg, n_replicas, _squeeze(state_b, 1),
            packed_b[:, 0], with_scalars)
        return _unsqueeze(st, 1), _unsqueeze(outs, 2)   # [K, Gl, 1, ...]

    spec = P(GROUP_AXIS, REPLICA_AXIS)
    mapped = _shard_map(
        per_device, mesh=mesh, in_specs=(spec, spec),
        out_specs=(spec, P(None, GROUP_AXIS, REPLICA_AXIS)))
    return _jit(mapped, donate)


def build_sim_step(cfg: LogConfig, n_replicas: int, *,
                   use_pallas: bool = False, interpret: bool = False,
                   donate: bool = True, fanout: str = "gather",
                   elections: bool = True, audit: bool = False,
                   telemetry: bool = False, txn: bool = False):
    """Compile the protocol step as an N-replica simulation on one device
    (``vmap`` with a named axis — identical collective semantics) over
    its ONE packed argument ``[R, rows, 128]``."""
    core = _replica_core(
        cfg, n_replicas, use_pallas=use_pallas, interpret=interpret,
        fanout=fanout, elections=elections, audit=audit,
        telemetry=telemetry, txn=txn)
    vstep = jax.vmap(core, in_axes=(0, 0), axis_name=REPLICA_AXIS)
    return _jit(_with_step_scalars(
        vstep, arg_layout(cfg, n_replicas, 1, txn)), donate)
