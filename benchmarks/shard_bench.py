#!/usr/bin/env python
"""Sharded multi-group throughput — the one-dispatch-per-step win.

Scales the group count G over {1, 2, 4, 8} (default) and measures
aggregate committed ops/s across ALL groups of a
:class:`~rdma_paxos_tpu.shard.cluster.ShardedCluster`, under a
saturating closed-loop workload (every group's leader fed a full batch
per step). The headline proof is the **dispatch count**: the
group-batched compiled step advances all G groups in ONE device
dispatch per protocol step — ``dispatch_per_step == 1.0`` regardless
of G — so aggregate throughput scales with G without multiplying host
dispatch overhead (the G-separate-clusters alternative pays G
dispatches per step).

Leaders are spread round-robin across the R replicas
(``place_leaders``), matching the production placement policy.

    python benchmarks/shard_bench.py --groups 1,2,4,8 --steps 60

Emits one standardized ``BENCH:`` line per G plus a scaling summary
(``benchmarks/reporting.emit``), and appends full registry-snapshot
rows to ``--json`` when given.
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def run_one(G: int, *, replicas: int, steps: int, payload: int,
            burst: bool, json_path, cfg=None, mesh=None,
            telemetry: bool = False, read_ratio: float = 0.0,
            zipf: float = 0.0, zipf_n_keys: int = 64,
            metric="shard_aggregate_committed_ops_per_sec",
            extra_detail=None, obs=None, on_cluster=None):
    """Build, warm, and drive one G-group cluster; returns the result
    row dict (also emitted as a BENCH: line). ``mesh=(group_shards,
    replicas)`` runs the MULTI-CHIP engine — state sharded over a real
    2-D ``(group, replica)`` device mesh instead of one device.
    ``telemetry=True`` compiles the device-counter step variants and
    adds per-group (and, on a mesh, per-SHARD) committed-entry device
    counters to the row — scaling provable from device truth alone."""
    from benchmarks.reporting import emit
    from rdma_paxos_tpu.config import LogConfig
    from rdma_paxos_tpu.obs import Observability
    from rdma_paxos_tpu.shard import ShardedCluster

    if cfg is None:
        cfg = LogConfig(n_slots=2048, slot_bytes=128,
                        window_slots=256, batch_slots=256)
    sc = ShardedCluster(cfg, replicas, G, mesh=mesh,
                        telemetry=telemetry)
    # a shared obs facade (--serve-metrics) keeps one registry across
    # the whole sweep so the live exporter's view survives cluster
    # swaps; on_cluster re-points the /healthz source at each new one
    sc.obs = obs if obs is not None else Observability()
    if on_cluster is not None:
        on_cluster(sc)
    targets = sc.place_leaders()
    B = cfg.batch_slots
    blob = b"x" * payload
    # read-mix column (read_ratio > 0): alongside every timed step's
    # write feed, each group's LEASEHOLDER serves a host-side batch of
    # lease reads sized read_ratio : (1-read_ratio) against the write
    # load — the per-group read fan-out place_leaders + leases buy,
    # visible per replica in the row
    kvs = None
    read_keys = None
    reads_per_step = 0
    if read_ratio > 0:
        from rdma_paxos_tpu.runtime import reads as reads_mod
        from rdma_paxos_tpu.shard.chaos import keys_for_groups
        from rdma_paxos_tpu.shard.kvs import ShardedKVS
        reads_mod.attach(sc)
        kvs = ShardedKVS(sc, cap=4096)
        read_keys = keys_for_groups(sc.router, 8, prefix=b"rmix")
        for g in range(G):
            for k in read_keys[g]:
                kvs.groups[g].put(sc.leader_hint(g), k, b"seed")
        sc.step()
        sc.step()
        # at least one read per group per step whenever the flag is
        # set (int() would truncate small ratios to zero and silently
        # disable the column), capped so extreme ratios stay feasible
        reads_per_step = max(1, min(
            int(B * read_ratio / max(1.0 - read_ratio, 1e-6)), 4 * B))

    # --zipf S: the offered load becomes KEY-shaped — each step offers
    # G*B ops whose keys are drawn Zipf(S) over a fixed pool and routed
    # by the router, so hot groups saturate their per-step batch while
    # cold ones idle. The row's zipf column carries offered vs admitted
    # per group — the skew the elastic-topology bench exists to fix.
    zipf_offered = [0] * G
    zipf_admitted = [0] * G
    if zipf:
        from benchmarks.arrival_traces import zipf_keys
        ztrace = zipf_keys((steps + 4) * G * B, s=zipf,
                           n_keys=zipf_n_keys, seed=0)
        key_group = {k: sc.router.group_of(k) for k in set(ztrace)}
        zstate = dict(pos=0)

    def feed():
        if zipf:
            sent = [0] * G
            take = ztrace[zstate["pos"]:zstate["pos"] + G * B]
            zstate["pos"] += len(take)
            for k in take:
                g = key_group[k]
                zipf_offered[g] += 1
                if sent[g] < B:
                    sent[g] += 1
                    zipf_admitted[g] += 1
                    sc.submit(g, sc.leader_hint(g), blob)
            return
        for g in range(G):
            lead = sc.leader_hint(g)
            for i in range(B):
                sc.submit(g, lead, blob)

    # warmup: compile both step variants (and the burst tiers when the
    # burst driver is measured) outside the timed window
    if burst:
        sc.prewarm()
    feed()
    sc.step()
    feed()
    sc.step()

    base_commit = [int(sc.last["commit"][g].max())
                   + int(sc.rebased_total[g]) for g in range(G)]
    d0, f0 = sc.dispatches, sc.fetch_dispatches
    n_dispatch_steps = 0
    reads_by_group = [0] * G
    reads_by_replica = [0] * replicas
    # zipf column: report the TIMED window only, not warmup
    zipf_offered = [0] * G
    zipf_admitted = [0] * G
    t0 = time.perf_counter()
    for _ in range(steps):
        feed()
        if burst:
            sc.step_burst()
        else:
            sc.step()
        n_dispatch_steps += 1
        if reads_per_step:
            from rdma_paxos_tpu.runtime.reads import count_read
            for g in range(G):
                holder = sc.leases.serving_holder(g)
                if holder < 0:
                    continue
                batch = (read_keys[g]
                         * (reads_per_step // len(read_keys[g]) + 1)
                         )[:reads_per_step]
                kvs.groups[g].get_many(holder, batch)
                count_read(sc.obs, "lease", holder, group=g,
                           n=len(batch))
                reads_by_group[g] += len(batch)
                reads_by_replica[holder] += len(batch)
    dt = time.perf_counter() - t0
    per_group = [int(sc.last["commit"][g].max())
                 + int(sc.rebased_total[g]) - base_commit[g]
                 for g in range(G)]
    committed = sum(per_group)
    dispatches = sc.dispatches - d0
    detail = dict(
        groups=G, replicas=replicas, steps=steps,
        driver=("burst" if burst else "step"),
        engine=("mesh" if mesh is not None else "single-device"),
        seconds=round(dt, 3),
        committed_total=committed,
        committed_per_group=per_group,
        leaders=targets,
        protocol_dispatches=dispatches,
        dispatch_per_step=round(dispatches
                                / max(n_dispatch_steps, 1), 3),
        replay_fetch_dispatches=sc.fetch_dispatches - f0,
        compiled_programs_used=len(sc.programs_used),
    )
    if telemetry:
        # device-truth committed work: the ON-DEVICE commit-advance
        # counter per group (max over the replica column — every
        # replica of a group advances the same committed prefix), and
        # its per-SHARD sums on a mesh (shard s owns the contiguous
        # group block [s*G/gs, (s+1)*G/gs) under P(group) sharding) —
        # the mesh scaling claim, provable without host bookkeeping
        from rdma_paxos_tpu.obs import device as device_mod
        col = device_mod.INDEX["committed_entries"]
        per_g = [int(sc.device_counters[g, :, col].max())
                 for g in range(G)]
        detail["device_committed_per_group"] = per_g
        if mesh is not None:
            gs = sc.mesh.devices.shape[0]
            blk = G // gs
            detail["device_committed_entries"] = [
                sum(per_g[s * blk:(s + 1) * blk]) for s in range(gs)]
    if reads_per_step:
        # honest ratio reporting: reads_per_step is capped at 4*B, so
        # at high requested ratios the EXECUTED mix can be leaner than
        # asked — the row carries both, never just the request
        total_reads = sum(reads_by_group)
        detail["read_mix"] = dict(
            requested_read_ratio=read_ratio,
            effective_read_ratio=round(
                total_reads / max(total_reads + committed, 1), 3),
            reads_per_group_per_step=reads_per_step,
            reads_total=total_reads,
            read_ops_per_sec=round(total_reads / dt, 1),
            reads_per_group=reads_by_group,
            # the fan-out column: lease reads served per REPLICA —
            # place_leaders spreads group leaseholds, so read serving
            # spreads with them instead of piling onto one replica
            reads_per_replica=reads_by_replica,
            lease_holders=sc.leases.holders())
    if zipf:
        # honest skew reporting: offered is the trace's routing truth,
        # admitted is what fit the per-step batch — the gap IS the
        # hot-group ceiling a static G cannot lift
        off_total = max(sum(zipf_offered), 1)
        detail["zipf"] = dict(
            s=zipf, n_keys=zipf_n_keys,
            offered_per_group=zipf_offered,
            admitted_per_group=zipf_admitted,
            dropped_total=sum(zipf_offered) - sum(zipf_admitted),
            hottest_offered_share=round(
                max(zipf_offered) / off_total, 3))
    if extra_detail:
        detail.update(extra_detail)
    row = emit(metric, round(committed / dt, 1), "ops/s",
               detail=detail, obs=sc.obs, json_path=json_path)
    label = (f"{mesh[0]}x{mesh[1]} mesh, G={G}" if mesh is not None
             else f"G={G}")
    print(f"  {label}: {committed} committed in {dt:.2f}s -> "
          f"{committed / dt:.0f} ops/s aggregate; "
          f"{dispatches} dispatches / {n_dispatch_steps} steps = "
          f"{dispatches / max(n_dispatch_steps, 1):.2f} per step; "
          f"leaders {targets}")
    return row


def run_mesh_sweep(layouts, *, groups_per_shard: int, steps: int,
                   payload: int, burst: bool, json_path,
                   read_ratio: float = 0.0, obs=None,
                   on_cluster=None) -> int:
    """The multi-chip layout sweep: each ``GSxR`` layout runs G =
    GS * groups_per_shard groups over a real ``(group, replica)``
    device mesh of GS*R devices, A/B'd against a SINGLE-chip baseline
    carrying the same per-shard load (groups_per_shard groups, the
    vmap engine). ``scaling_efficiency`` is the headline row:
    aggregate ÷ (GS × single-chip baseline aggregate) — 1.0 means
    every added device row contributed a full chip's worth of
    committed ops/s (near-linear scale-out in chips)."""
    import jax

    from benchmarks.reporting import emit

    n_dev = len(jax.devices())
    print(f"shard_bench mesh sweep: layouts {layouts}, "
          f"{groups_per_shard} group(s)/shard, {steps} steps, "
          f"driver={'burst' if burst else 'step'}, "
          f"{n_dev} devices available")
    baselines = {}          # R -> single-chip aggregate ops/s
    summary = {}
    for gs, R in layouts:
        if gs * R > n_dev:
            print(f"  {gs}x{R}: SKIPPED (needs {gs * R} devices, "
                  f"have {n_dev})")
            continue
        if R not in baselines:
            # telemetry ON for the baseline too: the A/B must compare
            # identical programs (counter overhead on both sides)
            base = run_one(
                groups_per_shard, replicas=R, steps=steps,
                payload=payload, burst=burst, json_path=json_path,
                telemetry=True, read_ratio=read_ratio,
                metric="mesh_baseline_committed_ops_per_sec",
                extra_detail=dict(role="single-chip baseline"),
                obs=obs, on_cluster=on_cluster)
            baselines[R] = base["value"]
        row = run_one(
            gs * groups_per_shard, replicas=R, steps=steps,
            payload=payload, burst=burst, json_path=json_path,
            mesh=(gs, R), telemetry=True, read_ratio=read_ratio,
            metric="mesh_aggregate_committed_ops_per_sec",
            extra_detail=dict(layout=f"{gs}x{R}", group_shards=gs,
                              devices=gs * R),
            obs=obs, on_cluster=on_cluster)
        eff = row["value"] / max(gs * baselines[R], 1e-9)
        emit("mesh_scaling_efficiency", round(eff, 3), "ratio",
             detail=dict(
                 layout=f"{gs}x{R}", group_shards=gs, replicas=R,
                 devices=gs * R, groups=gs * groups_per_shard,
                 aggregate_ops_per_sec=row["value"],
                 baseline_single_chip_ops_per_sec=baselines[R],
                 dispatch_per_step=row["detail"]["dispatch_per_step"],
                 device_committed_entries=row["detail"].get(
                     "device_committed_entries"),
                 driver=("burst" if burst else "step")),
             json_path=json_path)
        print(f"  {gs}x{R}: scaling efficiency {eff:.2f} "
              f"({row['value']:.0f} / ({gs} x {baselines[R]:.0f}))")
        summary[f"{gs}x{R}"] = dict(
            ops_per_sec=row["value"], scaling_efficiency=round(eff, 3),
            dispatch_per_step=row["detail"]["dispatch_per_step"])
    if not summary:
        # every layout was skipped: the artifact would carry no mesh
        # data — fail the run instead of handing CI a green no-op
        print(f"mesh sweep: NO layout fits the {n_dev} available "
              f"device(s) — nothing measured")
        return 1
    emit("mesh_scaling", detail=summary, json_path=json_path)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--groups", default=None,
                    help="comma-separated group counts to sweep "
                         "(default 1,2,4,8; incompatible with --mesh)")
    ap.add_argument("--replicas", type=int, default=None,
                    help="replication factor (default 3; in --mesh "
                         "mode R comes from each GSxR layout token)")
    ap.add_argument("--steps", type=int, default=60,
                    help="timed protocol steps per group count")
    ap.add_argument("--payload", type=int, default=64,
                    help="bytes per committed entry")
    ap.add_argument("--burst", action="store_true",
                    help="drive with fused multi-step bursts "
                         "(step_burst) instead of single steps")
    ap.add_argument("--mesh", default=None,
                    help='multi-chip sweep: comma-separated device-'
                         'mesh layouts "GSxR" (e.g. "1x2,2x2,4x2") — '
                         'each runs G = GS * --groups-per-shard '
                         'groups over a real (group, replica) mesh of '
                         'GS*R devices, emitting aggregate ops/s + '
                         'scaling_efficiency rows vs a single-chip '
                         'baseline')
    ap.add_argument("--groups-per-shard", type=int, default=1,
                    help="groups per device row in --mesh mode")
    ap.add_argument("--read-ratio", type=float, default=0.0,
                    help="read-mix column: serve this read fraction "
                         "as host-side lease reads at each group's "
                         "leaseholder alongside the write feed — the "
                         "per-group read fan-out shows up as "
                         "reads_per_replica in every row")
    ap.add_argument("--zipf", type=float, default=0.0, metavar="S",
                    help="key-shaped offered load: draw each step's "
                         "G*B ops from a Zipf(S) key pool routed by "
                         "the router (hot groups saturate, cold ones "
                         "idle) — adds the offered/admitted skew "
                         "column to every row")
    ap.add_argument("--zipf-keys", type=int, default=64,
                    help="distinct keys in the --zipf pool")
    ap.add_argument("--json", default=None,
                    help="append JSON result rows to this file")
    ap.add_argument("--serve-metrics", nargs="?", const=0,
                    default=None, type=int, metavar="PORT",
                    help="serve live /metrics + /healthz on this "
                         "localhost port for the whole sweep (no "
                         "value = ephemeral port) — watch a long "
                         "bench with the fleet console or any "
                         "Prometheus scraper")
    args = ap.parse_args(argv)

    os.environ.setdefault(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=8")
    import jax
    if os.environ.get("RP_BENCH_CPU", "1") == "1":
        jax.config.update("jax_platforms", "cpu")
    from rdma_paxos_tpu.utils.compile_cache import use_compile_cache
    use_compile_cache()

    from benchmarks.reporting import emit

    exporter = None
    shared_obs = None
    on_cluster = None
    if args.serve_metrics is not None:
        from rdma_paxos_tpu.obs import Observability
        from rdma_paxos_tpu.obs.export import OpsExporter
        shared_obs = Observability()
        holder = {}

        def on_cluster(sc):
            holder["c"] = sc
        exporter = OpsExporter(
            registry=shared_obs.metrics,
            health_fn=lambda: (holder["c"].health() if "c" in holder
                               else dict(ok=True)),
            port=args.serve_metrics).start()
        print(f"ops endpoints: {exporter.url}/metrics  /healthz")

    if args.mesh:
        if args.groups is not None or args.replicas is not None:
            # refuse loudly rather than silently drop: in --mesh mode
            # G and R come from the layout tokens + --groups-per-shard
            raise SystemExit(
                "--mesh is incompatible with --groups/--replicas: "
                "each GSxR layout fixes R, and G = GS * "
                "--groups-per-shard")
        layouts = []
        for tok in str(args.mesh).split(","):
            tok = tok.strip()
            if not tok:
                continue
            try:
                a, b = tok.lower().split("x")
                layouts.append((int(a), int(b)))
            except ValueError:
                raise SystemExit(
                    f"--mesh: bad layout {tok!r} — expected "
                    f'comma-separated "GSxR" tokens, e.g. "1x2,2x2,4x2"')
        rc = run_mesh_sweep(layouts,
                            groups_per_shard=args.groups_per_shard,
                            steps=args.steps, payload=args.payload,
                            burst=args.burst, json_path=args.json,
                            read_ratio=args.read_ratio,
                            obs=shared_obs, on_cluster=on_cluster)
        if exporter is not None:
            exporter.close()
        return rc

    if args.groups is None:
        args.groups = "1,2,4,8"
    if args.replicas is None:
        args.replicas = 3
    gs = [int(g) for g in str(args.groups).split(",") if g]
    print(f"shard_bench: G sweep {gs}, R={args.replicas}, "
          f"{args.steps} steps, "
          f"driver={'burst' if args.burst else 'step'}")
    scaling = {}
    for G in gs:
        row = run_one(G, replicas=args.replicas, steps=args.steps,
                      payload=args.payload, burst=args.burst,
                      json_path=args.json,
                      read_ratio=args.read_ratio,
                      zipf=args.zipf, zipf_n_keys=args.zipf_keys,
                      obs=shared_obs, on_cluster=on_cluster)
        scaling[G] = row
    emit("shard_scaling",
         detail={str(G): dict(
             ops_per_sec=scaling[G]["value"],
             dispatch_per_step=scaling[G]["detail"]["dispatch_per_step"])
             for G in gs},
         json_path=args.json)
    base = gs[0]
    for G in gs[1:]:
        speedup = scaling[G]["value"] / max(scaling[base]["value"], 1e-9)
        print(f"  aggregate G={G} vs G={base}: {speedup:.2f}x")
    if exporter is not None:
        exporter.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
