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
from jax.sharding import Mesh, PartitionSpec as P

from rdma_paxos_tpu.config import LogConfig
from rdma_paxos_tpu.consensus.state import ReplicaState, make_replica_state
from rdma_paxos_tpu.consensus.step import (
    GROUP_BATCH_AXIS, StepInput, replica_step, scan_readback, vmap_groups,
    with_scalars)

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


def _with_step_scalars(step):
    """A batched single step whose output carries its packed readback
    row. ``wraps`` keeps the step's name, which names the compiled
    program (``jit_replica_step``) in device traces."""
    @functools.wraps(step)
    def stepped(state_b, inp_b):
        st, out = step(state_b, inp_b)
        return st, with_scalars(out, out.accepted, st)
    return stepped


def _squeeze(tree):
    return jax.tree.map(lambda x: x[0], tree)


def _unsqueeze(tree):
    return jax.tree.map(lambda x: x[None], tree)


def build_spmd_step(cfg: LogConfig, n_replicas: int, mesh: Mesh, *,
                    use_pallas: bool = False, interpret: bool = False,
                    donate: bool = True, fanout: str = "gather",
                    elections: bool = True, audit: bool = False,
                    telemetry: bool = False, txn: bool = False):
    """Compile the protocol step over a real device mesh.

    Takes/returns *batched* pytrees (leading ``replica`` axis, sharded one
    row per device). State buffers are donated so the log arrays update
    in-place on device across steps — the analog of the reference's log
    living pinned in registered MRs (``rc_memory_reg``,
    ``dare_ibv_rc.c:240-276``).
    """
    core = functools.partial(
        replica_step, cfg=cfg, n_replicas=n_replicas,
        axis_name=REPLICA_AXIS, use_pallas=use_pallas, interpret=interpret,
        fanout=fanout, elections=elections, audit=audit,
        telemetry=telemetry, txn=txn)

    def per_device(state_b, inp_b):
        st, out = core(_squeeze(state_b), _squeeze(inp_b))
        return (_unsqueeze(st),
                _unsqueeze(with_scalars(out, out.accepted, st)))

    mapped = _shard_map(
        per_device, mesh=mesh,
        in_specs=(P(REPLICA_AXIS), P(REPLICA_AXIS)),
        out_specs=(P(REPLICA_AXIS), P(REPLICA_AXIS)))
    return jax.jit(mapped, donate_argnums=(0,) if donate else ())


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
    the pre-burst free space. K is the leading axis of the stacked
    inputs; returns the final state plus the per-step stacked outputs,
    whose ``scal`` (``[K, R, len(SCAN_KEYS) + R]``, ``accepted``
    cumulative in-program) is the ONE array the host reads: row
    ``[-1]`` is the burst's result."""
    import jax.numpy as jnp
    from jax import lax

    core = functools.partial(
        replica_step, cfg=cfg, n_replicas=n_replicas,
        axis_name=REPLICA_AXIS, use_pallas=use_pallas, interpret=interpret,
        fanout=fanout, elections=False, audit=audit,
        telemetry=telemetry)
    vstep = jax.vmap(core, in_axes=(0, 0), axis_name=REPLICA_AXIS)

    def burst(state_b, datas, metas, counts, peer_mask, applied, qdepth):
        # created in-trace, NOT closure-captured: a captured jnp array
        # is embedded in the lowered module as a literal (one more
        # constant per compiled tier), where an in-trace zeros is free
        zeros_r = jnp.zeros((n_replicas,), jnp.int32)
        # datas [K, R, B, sw]; metas [K, R, B, MW]; counts [K, R];
        # applied [R] = the HOST's true apply cursors, frozen across the
        # burst — echoing st.commit here would let pressure-gated (and
        # forced) pruning recycle slots the host has not replayed yet.
        # qdepth [R] = the host backlog REMAINING beyond this burst, so
        # the final step's gathered burst_hint keeps bursts back-to-back
        # under sustained load instead of resetting to zero
        def body(carry, xs):
            st, acc = carry
            d, m, c = xs
            inp = StepInput(
                batch_data=d, batch_meta=m, batch_count=c,
                timeout_fired=zeros_r, peer_mask=peer_mask,
                apply_done=applied, queue_depth=qdepth)
            st, out = vstep(st, inp)
            acc = acc + out.accepted
            return (st, acc), with_scalars(out, acc, st)
        (st, _acc), outs = lax.scan(body, (state_b, zeros_r),
                                    (datas, metas, counts))
        return st, outs
    return jax.jit(burst, donate_argnums=(0,) if donate else ())


def build_sim_scan(cfg: LogConfig, n_replicas: int, *,
                   replay_slots: int,
                   use_pallas: bool = False, interpret: bool = False,
                   donate: bool = True, fanout: str = "gather",
                   audit: bool = False, telemetry: bool = False):
    """The device-resident K-window scan tier: K fused protocol steps
    (the :func:`build_sim_burst` ``lax.scan``) returning ONE
    consolidated minimal readback instead of the full per-step output
    stacks — only what the host rules consume:

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
    import jax.numpy as jnp
    from jax import lax
    from rdma_paxos_tpu.consensus.log import extract_window

    core = functools.partial(
        replica_step, cfg=cfg, n_replicas=n_replicas,
        axis_name=REPLICA_AXIS, use_pallas=use_pallas,
        interpret=interpret, fanout=fanout, elections=False,
        audit=audit, telemetry=telemetry)
    vstep = jax.vmap(core, in_axes=(0, 0), axis_name=REPLICA_AXIS)
    vfetch = jax.vmap(lambda log, s: extract_window(
        log, s, replay_slots))

    def scan(state_b, datas, metas, counts, peer_mask, applied,
             qdepth):
        zeros_r = jnp.zeros((n_replicas,), jnp.int32)

        def body(carry, xs):
            st, acc = carry
            d, m, c = xs
            inp = StepInput(
                batch_data=d, batch_meta=m, batch_count=c,
                timeout_fired=zeros_r, peer_mask=peer_mask,
                apply_done=applied, queue_depth=qdepth)
            st, out = vstep(st, inp)
            acc = acc + out.accepted
            ys = scan_readback(out, acc, st, audit=audit,
                               telemetry=telemetry)
            return (st, acc), ys

        (st, _acc), ys = lax.scan(body, (state_b, zeros_r),
                                  (datas, metas, counts))
        wd, wm = vfetch(st.log, applied)
        ys["replay_data"] = wd
        ys["replay_meta"] = wm
        return st, ys
    return jax.jit(scan, donate_argnums=(0,) if donate else ())


def build_sim_group_scan(cfg: LogConfig, n_replicas: int, *,
                         replay_slots: int,
                         use_pallas: bool = False,
                         interpret: bool = False,
                         donate: bool = True, fanout: str = "gather",
                         audit: bool = False,
                         telemetry: bool = False):
    """:func:`build_sim_scan` with a leading ``group`` batch axis —
    the sharded engine's K-window scan tier (inputs shaped like
    :func:`build_sim_group_burst`; readback dict axes gain ``G``)."""
    import jax.numpy as jnp
    from jax import lax
    from rdma_paxos_tpu.consensus.log import extract_window
    from rdma_paxos_tpu.consensus.step import group_step

    gstep = group_step(cfg=cfg, n_replicas=n_replicas,
                       axis_name=REPLICA_AXIS, use_pallas=use_pallas,
                       interpret=interpret, fanout=fanout,
                       elections=False, audit=audit,
                       telemetry=telemetry)
    vfetch = jax.vmap(jax.vmap(lambda log, s: extract_window(
        log, s, replay_slots)))

    def scan(state_gb, datas, metas, counts, peer_mask, applied,
             qdepth):
        zeros_gr = jnp.zeros_like(counts[0])

        def body(carry, xs):
            st, acc = carry
            d, m, c = xs
            inp = StepInput(
                batch_data=d, batch_meta=m, batch_count=c,
                timeout_fired=zeros_gr, peer_mask=peer_mask,
                apply_done=applied, queue_depth=qdepth)
            st, out = gstep(st, inp)
            acc = acc + out.accepted
            ys = scan_readback(out, acc, st, audit=audit,
                               telemetry=telemetry)
            return (st, acc), ys

        (st, _acc), ys = lax.scan(body, (state_gb, zeros_gr),
                                  (datas, metas, counts))
        wd, wm = vfetch(st.log, applied)
        ys["replay_data"] = wd
        ys["replay_meta"] = wm
        return st, ys
    return jax.jit(scan, donate_argnums=(0,) if donate else ())


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
    import jax.numpy as jnp
    from jax import lax
    from rdma_paxos_tpu.consensus.log import extract_window

    core = functools.partial(
        replica_step, cfg=cfg, n_replicas=n_replicas,
        axis_name=REPLICA_AXIS, use_pallas=use_pallas,
        interpret=interpret, fanout=fanout, elections=False,
        audit=audit, telemetry=telemetry,
        group_batch_axis=GROUP_BATCH_AXIS)
    vcore = vmap_groups(core)                   # the device's own groups

    def per_device(state_b, datas_b, metas_b, counts_b, peer_b,
                   applied_b, qdepth_b):
        st = jax.tree.map(lambda x: x[:, 0], state_b)   # [Gl, ...]
        zeros_g = jnp.zeros_like(counts_b[0, :, 0])     # [Gl]

        def body(carry, xs):
            s, acc = carry
            d, m, c = xs                # d: [Gl, 1, B, sw] etc.
            inp = StepInput(
                batch_data=d[:, 0], batch_meta=m[:, 0],
                batch_count=c[:, 0], timeout_fired=zeros_g,
                peer_mask=peer_b[:, 0], apply_done=applied_b[:, 0],
                queue_depth=qdepth_b[:, 0])
            s, out = vcore(s, inp)
            acc = acc + out.accepted
            ys = scan_readback(out, acc, s, audit=audit,
                               telemetry=telemetry)
            return (s, acc), ys

        (st, _acc), ys = lax.scan(body, (st, zeros_g),
                                  (datas_b, metas_b, counts_b))
        wd, wm = jax.vmap(lambda log, s: extract_window(
            log, s, replay_slots))(st.log, applied_b[:, 0])
        out = {k: jax.tree.map(lambda x: x[:, :, None], v)
               for k, v in ys.items()}           # [K, Gl, 1, ...]
        out["replay_data"] = wd[:, None]
        out["replay_meta"] = wm[:, None]
        return (jax.tree.map(lambda x: x[:, None], st), out)

    spec_k = P(None, GROUP_AXIS, REPLICA_AXIS)
    out_spec = dict(scal=spec_k,
                    replay_data=P(GROUP_AXIS, REPLICA_AXIS),
                    replay_meta=P(GROUP_AXIS, REPLICA_AXIS))
    if audit:
        out_spec.update(audit_start=spec_k, audit_digest=spec_k,
                        audit_term=spec_k, audit_commit=spec_k)
    if telemetry:
        out_spec["telemetry"] = spec_k
    mapped = _shard_map(
        per_device, mesh=mesh,
        in_specs=(P(GROUP_AXIS, REPLICA_AXIS),
                  P(None, GROUP_AXIS, REPLICA_AXIS),
                  P(None, GROUP_AXIS, REPLICA_AXIS),
                  P(None, GROUP_AXIS, REPLICA_AXIS),
                  P(GROUP_AXIS, REPLICA_AXIS),
                  P(GROUP_AXIS, REPLICA_AXIS),
                  P(GROUP_AXIS, REPLICA_AXIS)),
        out_specs=(P(GROUP_AXIS, REPLICA_AXIS), out_spec))
    return jax.jit(mapped, donate_argnums=(0,) if donate else ())


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
    import jax.numpy as jnp
    from jax import lax
    from rdma_paxos_tpu.consensus.log import extract_window

    core = functools.partial(
        replica_step, cfg=cfg, n_replicas=n_replicas,
        axis_name=REPLICA_AXIS, use_pallas=use_pallas,
        interpret=interpret, fanout=fanout, elections=False,
        audit=audit, telemetry=telemetry)

    def per_device(state_b, datas_b, metas_b, counts_b, peer_b,
                   applied_b, qdepth_b):
        st = _squeeze(state_b)

        def body(carry, xs):
            s, acc = carry
            d, m, c = xs
            inp = StepInput(
                batch_data=d[0], batch_meta=m[0], batch_count=c[0],
                timeout_fired=jnp.zeros((), jnp.int32),
                peer_mask=peer_b[0], apply_done=applied_b[0],
                queue_depth=qdepth_b[0])
            s, out = core(s, inp)
            acc = acc + out.accepted
            ys = scan_readback(out, acc, s, audit=audit,
                               telemetry=telemetry)
            return (s, acc), ys

        (st, _acc), ys = lax.scan(
            body, (st, jnp.zeros((), jnp.int32)),
            (datas_b, metas_b, counts_b))
        wd, wm = extract_window(st.log, applied_b[0], replay_slots)
        out = {k: jax.tree.map(lambda x: x[:, None], v)
               for k, v in ys.items()}           # [K, 1, ...]
        out["replay_data"] = wd[None]
        out["replay_meta"] = wm[None]
        return _unsqueeze(st), out

    spec_k = P(None, REPLICA_AXIS)
    out_spec = dict(scal=spec_k,
                    replay_data=P(REPLICA_AXIS),
                    replay_meta=P(REPLICA_AXIS))
    if audit:
        out_spec.update(audit_start=spec_k, audit_digest=spec_k,
                        audit_term=spec_k, audit_commit=spec_k)
    if telemetry:
        out_spec["telemetry"] = spec_k
    mapped = _shard_map(
        per_device, mesh=mesh,
        in_specs=(P(REPLICA_AXIS), P(None, REPLICA_AXIS),
                  P(None, REPLICA_AXIS), P(None, REPLICA_AXIS),
                  P(REPLICA_AXIS), P(REPLICA_AXIS), P(REPLICA_AXIS)),
        out_specs=(P(REPLICA_AXIS), out_spec))
    return jax.jit(mapped, donate_argnums=(0,) if donate else ())


def build_spmd_burst(cfg: LogConfig, n_replicas: int, mesh: Mesh, *,
                     use_pallas: bool = False, interpret: bool = False,
                     donate: bool = True, fanout: str = "gather",
                     audit: bool = False,
                     telemetry: bool = False):
    """:func:`build_sim_burst` over a real device mesh (``shard_map`` with
    the K-step scan inside the per-device program); the same stacked
    outputs, ``scal`` the one array the host reads."""
    import jax.numpy as jnp
    from jax import lax

    core = functools.partial(
        replica_step, cfg=cfg, n_replicas=n_replicas,
        axis_name=REPLICA_AXIS, use_pallas=use_pallas, interpret=interpret,
        fanout=fanout, elections=False, audit=audit,
        telemetry=telemetry)

    def per_device(state_b, datas_b, metas_b, counts_b, peer_b,
                   applied_b, qdepth_b):
        st = _squeeze(state_b)

        def body(carry, xs):
            s, acc = carry
            d, m, c = xs
            inp = StepInput(
                batch_data=d[0], batch_meta=m[0], batch_count=c[0],
                timeout_fired=jnp.zeros((), jnp.int32),
                peer_mask=peer_b[0], apply_done=applied_b[0],
                # remaining backlog rides every burst step's gather so
                # the final burst_hint sustains back-to-back bursts
                queue_depth=qdepth_b[0])
            s, out = core(s, inp)
            acc = acc + out.accepted
            return (s, acc), with_scalars(out, acc, s)
        (st, _acc), outs = lax.scan(
            body, (st, jnp.zeros((), jnp.int32)),
            (datas_b, metas_b, counts_b))
        return (_unsqueeze(st),
                jax.tree.map(lambda x: x[:, None], outs))   # [K, 1, ...]

    mapped = _shard_map(
        per_device, mesh=mesh,
        in_specs=(P(REPLICA_AXIS), P(None, REPLICA_AXIS),
                  P(None, REPLICA_AXIS), P(None, REPLICA_AXIS),
                  P(REPLICA_AXIS), P(REPLICA_AXIS), P(REPLICA_AXIS)),
        out_specs=(P(REPLICA_AXIS), P(None, REPLICA_AXIS)))
    return jax.jit(mapped, donate_argnums=(0,) if donate else ())


def build_sim_group_step(cfg: LogConfig, n_replicas: int, *,
                         use_pallas: bool = False, interpret: bool = False,
                         donate: bool = True, fanout: str = "gather",
                         elections: bool = True, audit: bool = False,
                         telemetry: bool = False, txn: bool = False):
    """Compile the G-group × R-replica protocol step as ONE program on
    one device (:func:`rdma_paxos_tpu.consensus.step.group_step` under
    ``jit``). The group axis is a batch axis — groups are independent;
    only the replica axis carries protocol collectives (the group axis
    is named for one scalar, the rescan's gate: ``group_step``) — so
    one dispatch steps every group (the sharded-cluster hot path)."""
    from rdma_paxos_tpu.consensus.step import group_step
    gstep = group_step(cfg=cfg, n_replicas=n_replicas,
                       axis_name=REPLICA_AXIS, use_pallas=use_pallas,
                       interpret=interpret, fanout=fanout,
                       elections=elections, audit=audit,
                       telemetry=telemetry, txn=txn)
    return jax.jit(_with_step_scalars(gstep),
                   donate_argnums=(0,) if donate else ())


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
    per group. Inputs: datas ``[K, G, R, B, sw]``, metas
    ``[K, G, R, B, MW]``, counts ``[K, G, R]``, peer_mask
    ``[G, R, R]``, applied/qdepth ``[G, R]``."""
    import jax.numpy as jnp
    from jax import lax
    from rdma_paxos_tpu.consensus.step import group_step

    gstep = group_step(cfg=cfg, n_replicas=n_replicas,
                       axis_name=REPLICA_AXIS, use_pallas=use_pallas,
                       interpret=interpret, fanout=fanout,
                       elections=False, audit=audit,
                       telemetry=telemetry)

    def burst(state_gb, datas, metas, counts, peer_mask, applied, qdepth):
        zeros_gr = jnp.zeros_like(counts[0])

        def body(carry, xs):
            st, acc = carry
            d, m, c = xs
            inp = StepInput(
                batch_data=d, batch_meta=m, batch_count=c,
                timeout_fired=zeros_gr, peer_mask=peer_mask,
                apply_done=applied, queue_depth=qdepth)
            st, out = gstep(st, inp)
            acc = acc + out.accepted
            return (st, acc), with_scalars(out, acc, st)
        (st, _acc), outs = lax.scan(body, (state_gb, zeros_gr),
                                    (datas, metas, counts))
        return st, outs
    return jax.jit(burst, donate_argnums=(0,) if donate else ())


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

    Axis layout: the global ``[G, R, ...]`` pytrees are sharded
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
    core = functools.partial(
        replica_step, cfg=cfg, n_replicas=n_replicas,
        axis_name=REPLICA_AXIS, use_pallas=use_pallas,
        interpret=interpret, fanout=fanout, elections=elections,
        audit=audit, telemetry=telemetry, txn=txn,
        group_batch_axis=GROUP_BATCH_AXIS)
    vcore = vmap_groups(core)                   # the device's own groups

    def per_device(state_b, inp_b):
        st, out = vcore(jax.tree.map(lambda x: x[:, 0], state_b),
                        jax.tree.map(lambda x: x[:, 0], inp_b))
        out = with_scalars(out, out.accepted, st)
        return (jax.tree.map(lambda x: x[:, None], st),
                jax.tree.map(lambda x: x[:, None], out))

    mapped = _shard_map(
        per_device, mesh=mesh,
        in_specs=(P(GROUP_AXIS, REPLICA_AXIS),
                  P(GROUP_AXIS, REPLICA_AXIS)),
        out_specs=(P(GROUP_AXIS, REPLICA_AXIS),
                   P(GROUP_AXIS, REPLICA_AXIS)))
    return jax.jit(mapped, donate_argnums=(0,) if donate else ())


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
    sized by the caller — applied per group. Input shapes match
    :func:`build_sim_group_burst`; K is unsharded, ``[G, R]`` axes are
    sharded ``P(group, replica)``."""
    import jax.numpy as jnp
    from jax import lax

    core = functools.partial(
        replica_step, cfg=cfg, n_replicas=n_replicas,
        axis_name=REPLICA_AXIS, use_pallas=use_pallas,
        interpret=interpret, fanout=fanout, elections=False,
        audit=audit, telemetry=telemetry,
        group_batch_axis=GROUP_BATCH_AXIS)
    vcore = vmap_groups(core)                   # the device's own groups

    def per_device(state_b, datas_b, metas_b, counts_b, peer_b,
                   applied_b, qdepth_b):
        st = jax.tree.map(lambda x: x[:, 0], state_b)   # [Gl, ...]
        zeros_g = jnp.zeros_like(counts_b[0, :, 0])     # [Gl]

        def body(carry, xs):
            s, acc = carry
            d, m, c = xs                # d: [Gl, 1, B, sw] etc.
            inp = StepInput(
                batch_data=d[:, 0], batch_meta=m[:, 0],
                batch_count=c[:, 0], timeout_fired=zeros_g,
                peer_mask=peer_b[:, 0], apply_done=applied_b[:, 0],
                queue_depth=qdepth_b[:, 0])
            s, out = vcore(s, inp)
            acc = acc + out.accepted
            return (s, acc), with_scalars(out, acc, s)
        (st, _acc), outs = lax.scan(body, (st, zeros_g),
                                    (datas_b, metas_b, counts_b))
        return (jax.tree.map(lambda x: x[:, None], st),
                jax.tree.map(lambda x: x[:, :, None], outs))

    mapped = _shard_map(
        per_device, mesh=mesh,
        in_specs=(P(GROUP_AXIS, REPLICA_AXIS),
                  P(None, GROUP_AXIS, REPLICA_AXIS),
                  P(None, GROUP_AXIS, REPLICA_AXIS),
                  P(None, GROUP_AXIS, REPLICA_AXIS),
                  P(GROUP_AXIS, REPLICA_AXIS),
                  P(GROUP_AXIS, REPLICA_AXIS),
                  P(GROUP_AXIS, REPLICA_AXIS)),
        out_specs=(P(GROUP_AXIS, REPLICA_AXIS),
                   P(None, GROUP_AXIS, REPLICA_AXIS)))
    return jax.jit(mapped, donate_argnums=(0,) if donate else ())


def build_sim_step(cfg: LogConfig, n_replicas: int, *,
                   use_pallas: bool = False, interpret: bool = False,
                   donate: bool = True, fanout: str = "gather",
                   elections: bool = True, audit: bool = False,
                   telemetry: bool = False, txn: bool = False):
    """Compile the protocol step as an N-replica simulation on one device
    (``vmap`` with a named axis — identical collective semantics)."""
    core = functools.partial(
        replica_step, cfg=cfg, n_replicas=n_replicas,
        axis_name=REPLICA_AXIS, use_pallas=use_pallas, interpret=interpret,
        fanout=fanout, elections=elections, audit=audit,
        telemetry=telemetry, txn=txn)
    vstep = jax.vmap(core, in_axes=(0, 0), axis_name=REPLICA_AXIS)
    return jax.jit(_with_step_scalars(vstep),
                   donate_argnums=(0,) if donate else ())
