#!/usr/bin/env python
"""Elastic-split payoff — autonomous topology vs every static G.

The device group count G is frozen at compile time, so the classic
answer to a skewed keyspace is "pick a better G up front". This bench
shows why that answer loses: under a Zipf-shaped offered load
(``arrival_traces.zipf_keys``) the hottest keys hash into ONE group
whose per-step batch ceiling caps aggregate admission no matter which
static G you picked, while the SAME cluster with the topology policy
attached detects the sustained skew (stock ``topology_group_skew``
rule → ``AlertEngine.add_hook`` → ``propose_split``), carves the hot
range out online, and admits what the static ceilings dropped.

Methodology — alternating best-of rounds on fresh clusters (the
shared A/B discipline): each round runs every static-G variant and
the autonomous variant once, interleaved; each variant keeps its best
round. The headline ``topology_split_speedup`` row is autonomous
ops/s over the BEST static G's ops/s, with the policy/controller
evidence (transitions, installed rules, per-group admission) in the
detail — a ratio above 1.0 means the online split beat every
compile-time G choice on the identical offered trace.

Admission (client puts accepted into group logs during the timed
window) is the measured rate: topology SEED records are protocol
traffic, not client work, so counting committed entries would flatter
the autonomous variant; admission counts only what the client got in.
The unit is ops per PROTOCOL STEP, not wall seconds: a protocol step
is one fused device dispatch regardless of G (``dispatch_per_step ==
1.0`` — shard_bench's headline), so the step is the clock on which
all G choices cost the same on the real device, while host-simulated
step wall time grows with G and would bias the cross-G comparison.
Step-domain admission is also fully deterministic — the CI smoke
re-derives the identical ratio. Wall ops/s rides in each row's detail.

    python benchmarks/topology_bench.py --steps 160 --rounds 2
"""

import argparse
import os
import sys
import time
from collections import deque

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def run_variant(G: int, *, topo: bool, steps: int,
                offered_per_step: int, zipf_s: float, zipf_n_keys: int,
                replicas: int = 3, skew_ratio: float = 1.5,
                adapt_steps: int = 120, cfg=None):
    """One fresh cluster driven through the seeded Zipf trace; returns
    (admitted_ops_per_step, evidence_detail). ``adapt_steps`` run the
    identical offered load UNTIMED first — the autonomous variant
    detects the skew and completes its transitions there, the statics
    reach their backlogged steady state — so the timed window compares
    converged behavior, not transition transients."""
    from benchmarks.arrival_traces import zipf_keys
    from rdma_paxos_tpu.config import LogConfig
    from rdma_paxos_tpu.obs import AlertEngine, Observability
    from rdma_paxos_tpu.runtime import reads as reads_mod
    from rdma_paxos_tpu.shard import ShardedCluster
    from rdma_paxos_tpu.shard.kvs import ShardedKVS

    if cfg is None:
        cfg = LogConfig(n_slots=1024, slot_bytes=128,
                        window_slots=32, batch_slots=8)
    sc = ShardedCluster(cfg, replicas, G)
    obs = Observability()
    sc.obs = obs
    kvs = ShardedKVS(sc, cap=4096)
    reads_mod.attach(sc)
    ctl = engine = None
    if topo:
        from rdma_paxos_tpu.topology import attach_topology
        from rdma_paxos_tpu.topology.policy import TopologyPolicy
        engine = AlertEngine(obs.metrics, rules=[])
        pol = TopologyPolicy(window=16, skew_ratio=skew_ratio,
                             for_evals=4, cooldown_evals=8)
        ctl = attach_topology(kvs, policy=pol, alerts=engine,
                              cooldown_steps=8)
    sc.place_leaders()
    B = cfg.batch_slots
    blob = b"x" * 32
    trace = zipf_keys(offered_per_step * (adapt_steps + steps + 68),
                      s=zipf_s, n_keys=zipf_n_keys, seed=0)
    admitted_pg = [0] * G
    clock = dict(t=0)

    def pump_step(pending) -> int:
        """One protocol step: admit pending client puts up to the
        per-group batch ceiling (frozen-range keys deferred while the
        transition window holds them), then step + drive + evaluate."""
        sent = [0] * G
        kept = []
        # bounded head scan: routing every backlogged key every step
        # would charge variants O(backlog) host work — the cap makes
        # the per-step scan cost identical across variants
        scanned, limit = 0, 4 * G * B
        while pending and scanned < limit:
            k = pending.popleft()
            scanned += 1
            if ctl is not None and ctl.would_block(k):
                kept.append(k)
                continue
            g = kvs.group_of(k)
            if sent[g] >= B:
                kept.append(k)
                continue
            kvs.groups[g].put(sc.leader_hint(g), k, blob)
            sent[g] += 1
            admitted_pg[g] += 1
        pending.extendleft(reversed(kept))      # keep FIFO order
        sc.step()
        clock["t"] += 1
        if ctl is not None:
            ctl.drive()
            # drivers evaluate alerts on a poll cadence, not per step
            # — a full registry snapshot every step would charge the
            # autonomous variant host work no deployment pays
            if clock["t"] % 4 == 0:
                engine.evaluate()
        return sum(sent)

    # warmup: every pool key written once (the split's median scan
    # reads the keyspace from the store) + compile both step variants
    seedq = deque(sorted(set(trace)))
    while seedq:
        pump_step(seedq)
    sc.step()
    sc.step()
    for g in range(G):
        admitted_pg[g] = 0

    pending = deque()
    pos = 0
    for _ in range(adapt_steps):        # untimed: converge first
        pending.extend(trace[pos:pos + offered_per_step])
        pos += offered_per_step
        pump_step(pending)
    # close out any transition still open at the adaptation boundary
    # (bounded): the timed window measures the converged routing, not
    # a half-seeded one
    closeout = 0
    while (ctl is not None and ctl.in_window() and closeout < 64):
        pending.extend(trace[pos:pos + offered_per_step])
        pos += offered_per_step
        pump_step(pending)
        closeout += 1
    for g in range(G):
        admitted_pg[g] = 0
    admitted = 0
    timed_base = pos
    t0 = time.perf_counter()
    for _ in range(steps):
        pending.extend(trace[pos:pos + offered_per_step])
        pos += offered_per_step
        admitted += pump_step(pending)
    dt = time.perf_counter() - t0
    detail = dict(
        groups=G, replicas=replicas, steps=steps,
        adapt_steps=adapt_steps, closeout_steps=closeout,
        autonomous=topo, seconds=round(dt, 3),
        wall_ops_per_sec=round(admitted / dt, 1),
        offered=pos - timed_base, admitted=admitted,
        backlog_end=len(pending),
        admitted_per_group=admitted_pg,
        zipf=dict(s=zipf_s, n_keys=zipf_n_keys))
    if ctl is not None:
        st = ctl.status()
        detail["topology"] = dict(
            transitions=st["transitions_total"],
            abandoned=st["abandoned_total"],
            epoch=st["epoch"],
            overrides=[r.to_dict() for r in kvs.router.overrides],
            policy=st["policy"])
    return admitted / steps, detail


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--static-groups", default="2,4",
                    help="static G values the autonomous variant "
                         "must beat (comma-separated)")
    ap.add_argument("--topo-groups", type=int, default=4,
                    help="G for the autonomous (policy-attached) run")
    ap.add_argument("--replicas", type=int, default=3)
    ap.add_argument("--steps", type=int, default=160,
                    help="timed protocol steps per variant")
    ap.add_argument("--offered", type=int, default=24,
                    help="client puts offered per step")
    ap.add_argument("--zipf-s", type=float, default=0.9,
                    help="Zipf exponent of the offered key shape")
    ap.add_argument("--zipf-keys", type=int, default=32,
                    help="distinct keys in the pool")
    ap.add_argument("--rounds", type=int, default=2,
                    help="alternating best-of rounds per variant")
    ap.add_argument("--json", default=None,
                    help="append JSON result rows to this file")
    args = ap.parse_args(argv)

    os.environ.setdefault(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=8")
    import jax
    if os.environ.get("RP_BENCH_CPU", "1") == "1":
        jax.config.update("jax_platforms", "cpu")
    from rdma_paxos_tpu.utils.compile_cache import use_compile_cache
    use_compile_cache()

    from benchmarks.reporting import emit

    static_gs = [int(g) for g in str(args.static_groups).split(",")
                 if g]
    variants = [(f"static_G{g}", g, False) for g in static_gs]
    variants.append((f"auto_G{args.topo_groups}", args.topo_groups,
                     True))
    kw = dict(steps=args.steps, offered_per_step=args.offered,
              zipf_s=args.zipf_s, zipf_n_keys=args.zipf_keys,
              replicas=args.replicas)
    print(f"topology_bench: static G {static_gs} vs autonomous "
          f"G={args.topo_groups}, zipf s={args.zipf_s} over "
          f"{args.zipf_keys} keys, {args.offered} offered/step, "
          f"{args.steps} steps x {args.rounds} round(s)")
    best = {}
    for r in range(args.rounds):
        for label, G, topo in variants:      # alternating best-of
            ops, detail = run_variant(G, topo=topo, **kw)
            print(f"  round {r} {label}: {ops:.2f} admitted ops/step "
                  f"(backlog {detail['backlog_end']}, "
                  f"{detail['wall_ops_per_sec']:.0f} wall ops/s)")
            if label not in best or ops > best[label][0]:
                best[label] = (ops, detail)
    for label, (ops, detail) in best.items():
        emit("topology_variant_admitted_ops_per_step", round(ops, 2),
             "ops/step", detail=dict(variant=label, **detail),
             json_path=args.json)
    auto_label = variants[-1][0]
    auto_ops, auto_detail = best[auto_label]
    stat_label = max((l for l in best if l != auto_label),
                     key=lambda l: best[l][0])
    speedup = auto_ops / max(best[stat_label][0], 1e-9)
    emit("topology_split_speedup", round(speedup, 3), "ratio",
         detail=dict(
             autonomous=auto_label,
             autonomous_ops_per_step=round(auto_ops, 2),
             best_static=stat_label,
             best_static_ops_per_step=round(best[stat_label][0], 2),
             statics={l: round(best[l][0], 2) for l in best
                      if l != auto_label},
             transitions=auto_detail.get("topology", {}).get(
                 "transitions"),
             overrides=auto_detail.get("topology", {}).get(
                 "overrides")),
         json_path=args.json)
    print(f"  speedup: {auto_label} {auto_ops:.2f} vs best static "
          f"{stat_label} {best[stat_label][0]:.2f} ops/step "
          f"-> {speedup:.2f}x")
    return 0


if __name__ == "__main__":
    sys.exit(main())
