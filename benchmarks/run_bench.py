#!/usr/bin/env python
"""Replicated-application benchmark — the ``benchmarks/run.sh`` analog.

Boots N replicas of the unmodified toyserver under LD_PRELOAD interposition
+ the in-process consensus driver, finds the leader (same '] LEADER' grep
contract as the reference, or the driver API), then drives a SET/GET
workload against the leader's app — measuring committed-op throughput and
client-visible latency percentiles end to end through the full stack:
client TCP -> app read() -> shim -> UDS -> consensus step -> quorum commit
-> ack -> app reply.

    python benchmarks/run_bench.py --replicas 3 --requests 2000 --clients 4
"""

import argparse
import os
import socket
import subprocess
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

NATIVE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "native")


def _measure_flag_overhead(flag, proof, cfg=None, *, n_replicas=3,
                           steps=300, per_step=8, payload=64,
                           warmup=10, repeats=3, fanout="psum",
                           make=None, after_step=None):
    """The shared compiled-step-flag A/B harness: drive the identical
    closed-loop workload through a flag-off and a flag-on
    ``SimCluster`` and compare committed-entry throughput. The two
    variants run ALTERNATING for ``repeats`` rounds and each variant
    scores its fastest round (host-load noise on a shared machine
    easily exceeds the effect being measured). ``proof(on_cluster,
    out)`` attaches the flag-specific evidence the row carries.
    Returns ``{"off": {...}, "on": {...}, "overhead_pct": ...}`` (the
    <5% acceptance target the overhead bench rows share).

    ``make(variant, cfg, n_replicas)`` overrides cluster construction
    (for overheads that are not a bare SimCluster flag — e.g. the
    repair controller) and ``after_step(variant, cluster)`` runs after
    every step, both rounds identical except the measured delta —
    the one methodology all overhead rows share."""
    import time as _t

    from rdma_paxos_tpu.config import LogConfig
    from rdma_paxos_tpu.runtime.sim import SimCluster

    if cfg is None:
        cfg = LogConfig(n_slots=512, slot_bytes=128, window_slots=64,
                        batch_slots=16)
    blob = b"x" * payload
    clusters = {}
    for variant in ("off", "on"):
        if make is not None:
            c = make(variant, cfg, n_replicas)
        else:
            c = SimCluster(cfg, n_replicas, fanout=fanout,
                           **{flag: variant == "on"})
            c.run_until_elected(0)
        for _ in range(warmup):
            c.submit(0, blob)
            c.step()
            if after_step is not None:
                after_step(variant, c)
        clusters[variant] = c
    out = {v: dict(steps=steps, seconds=None, committed=None,
                   ops_per_sec=0.0) for v in clusters}
    for _ in range(repeats):
        for variant, c in clusters.items():
            base = int(c.last["commit"].max()) + c.rebased_total
            t0 = _t.perf_counter()
            for _ in range(steps):
                for _ in range(per_step):
                    c.submit(0, blob)
                c.step()
                if after_step is not None:
                    after_step(variant, c)
            dt = _t.perf_counter() - t0
            done = int(c.last["commit"].max()) + c.rebased_total - base
            ops = round(done / dt, 1)
            if ops > out[variant]["ops_per_sec"]:
                out[variant] = dict(steps=steps, seconds=round(dt, 4),
                                    committed=done, ops_per_sec=ops)
    proof(clusters["on"], out)
    off, on = out["off"]["ops_per_sec"], out["on"]["ops_per_sec"]
    out["overhead_pct"] = round((off - on) / off * 100, 2)
    return out


def measure_host_path(cfg=None, *, n_replicas=3, steps=40,
                      per_step=2000, payload=24, warmup=4, repeats=4,
                      scan_k=8):
    """The host-data-plane A/B on the engine closed loop (the
    ``_measure_flag_overhead`` methodology — prewarmed clusters,
    ALTERNATING best-of rounds, same core): identical burst-driven
    workload through

    * ``off`` — the scalar reference host loops (per-entry pack /
      decode / replay-plan) + the plain burst path (per-field stacked
      readback + standalone replay-fetch dispatches);
    * ``on``  — the vectorized window batch ops + the device-resident
      K-window scan tier (one consolidated readback, replay rows
      in-dispatch).

    Committed-entries/s per variant, the speedup, and the scan's
    dispatch accounting (scan vs fetch dispatches) ride the row."""
    import time as _t

    from rdma_paxos_tpu.config import LogConfig
    from rdma_paxos_tpu.runtime import hostpath
    from rdma_paxos_tpu.runtime.sim import SimCluster, cap_scan_tiers

    if cfg is None:
        # the small-SET geometry: 64-byte slots fit a redis-style SET
        # fragment, and the thin window keeps the XLA-CPU window
        # programs from drowning the host-path delta being measured
        cfg = LogConfig(n_slots=32768, slot_bytes=64,
                        window_slots=1024, batch_slots=1024)
    blob = b"x" * payload
    clusters = {}
    for variant in ("off", "on"):
        c = SimCluster(cfg, n_replicas, fanout="psum")
        cap_scan_tiers(c, scan_k)
        c.run_until_elected(0)
        c.scan = variant == "on"   # prewarm compiles the ON tiers too
        c.prewarm()
        for _ in range(warmup):
            c.submit_many(0, [(3, 1, 0, blob)] * per_step)
            c.step_burst()
        clusters[variant] = c
    out = {v: dict(steps=steps, seconds=None, committed=None,
                   ops_per_sec=0.0) for v in clusters}
    for _ in range(repeats):
        for variant, c in clusters.items():
            hostpath.set_vectorized(variant == "on")
            base = int(c.last["commit"].max()) + c.rebased_total
            t0 = _t.perf_counter()
            for _ in range(steps):
                c.submit_many(0, [(3, 1, 0, blob)] * per_step)
                c.step_burst()
            while (int(c.last["commit"].min())
                   < int(c.last["end"].max())):
                c.step_burst()
            dt = _t.perf_counter() - t0
            done = (int(c.last["commit"].max()) + c.rebased_total
                    - base)
            ops = round(done / dt, 1)
            if ops > out[variant]["ops_per_sec"]:
                out[variant] = dict(steps=steps, seconds=round(dt, 4),
                                    committed=done, ops_per_sec=ops)
    hostpath.set_vectorized(True)
    on_c = clusters["on"]
    out["scan"] = dict(scan_dispatches=int(on_c.scan_dispatches),
                       scan_k=max(on_c.K_TIERS))
    out["speedup"] = round(
        out["on"]["ops_per_sec"]
        / max(out["off"]["ops_per_sec"], 1e-9), 3)
    return out


def measure_governor(trace_shape="bursty", cfg=None, *, n_replicas=3,
                     ticks=400, seed=0, repeats=3, payload=24,
                     hi=None, scan=False):
    """The adaptive-dispatch A/B on the engine closed loop: one seeded
    arrival trace (``benchmarks/arrival_traces.py``) replayed
    IDENTICALLY through

    * every static geometry on the ladder — the serial single step
      and each burst tier cap K (each variant dispatches every tick,
      the driver-poll analog: an idle tick still costs a heartbeat
      dispatch, which is exactly the idle bias being measured); and
    * the governed variant — the :class:`DispatchGovernor` picks the
      tier per tick, skips the dispatch entirely on idle ticks
      (quiescence), and holds admission for a bounded beat when the
      window is filling (coalescing).

    Alternating best-of rounds (the shared A/B methodology). Emitted:
    ``governor_speedup`` = governed committed-ops/s over the BEST
    single static geometry for this trace, and ``governor_p99_ratio``
    = governed per-entry commit-latency p99 over that same best
    static variant's (<= 1.1 acceptance: throughput is never bought
    with latency). The governed cluster's ``governor_tier`` trace
    events ride the result (the CI failure artifact)."""
    import collections as _coll
    import time as _t

    from benchmarks.arrival_traces import make_trace
    from rdma_paxos_tpu.config import LogConfig
    from rdma_paxos_tpu.obs import Observability
    from rdma_paxos_tpu.runtime.governor import attach_governor
    from rdma_paxos_tpu.runtime.sim import SimCluster

    if cfg is None:
        cfg = LogConfig(n_slots=4096, slot_bytes=64, window_slots=256,
                        batch_slots=64)
    B = cfg.batch_slots
    arrivals = make_trace(trace_shape, ticks, seed=seed, lo=0,
                          hi=(hi or 3 * B))
    total_entries = sum(arrivals)
    blob = b"x" * payload

    clusters = {}
    variants = ["serial"] + [f"burst{k}" for k in SimCluster.K_TIERS]
    for v in variants + ["governed"]:
        c = SimCluster(cfg, n_replicas, fanout="psum", scan=scan)
        c.run_until_elected(0)
        gov = None
        if v == "governed":
            c.obs = Observability()
            gov = attach_governor(c, obs=c.obs)
        c.prewarm()
        clusters[v] = (c, gov)

    def committed(c):
        return int(c.last["commit"].max()) + c.rebased_total

    def run_round(v):
        c, gov = clusters[v]
        base = committed(c)
        submitted = 0
        waiting = _coll.deque()    # (abs target index, t_submit, n)
        lats = []                  # (latency_s, n)
        coalesce_run = 0

        def harvest():
            done = committed(c) - base
            now = _t.perf_counter()
            while waiting and waiting[0][0] <= done:
                tgt, ts, n = waiting.popleft()
                lats.append((now - ts, n))

        def dispatch():
            nonlocal coalesce_run
            coalesce_run = 0
            if v == "serial":
                c.step()
            elif v == "governed":
                d = gov.decision
                if d.max_k > 1 and len(c.pending[0]):
                    c.step_burst(max_k=d.max_k)
                else:
                    c.step()
            else:
                k = int(v[len("burst"):])
                if len(c.pending[0]):
                    c.step_burst(max_k=k)
                else:
                    c.step()        # idle heartbeat dispatch
            harvest()

        t0 = _t.perf_counter()
        for n in arrivals:
            if n:
                c.submit_many(0, [(3, 1, 0, blob)] * n)
                submitted += n
                waiting.append((submitted, _t.perf_counter(), n))
            if v == "governed":
                backlog = len(c.pending[0])
                if backlog == 0 and not waiting:
                    continue        # idle quiescence: no dispatch
                d = gov.decision
                if (d.coalesce_us > 0 and coalesce_run < 3
                        and 0 < backlog < d.max_k * B // 2):
                    coalesce_run += 1
                    continue        # bounded admission coalesce
            dispatch()
        while committed(c) - base < submitted:
            dispatch()
        dt = _t.perf_counter() - t0
        weight = sum(n for _, n in lats)
        p99 = 0.0
        if weight:
            need = 0.99 * weight
            cum = 0
            for lat, n in sorted(lats):
                cum += n
                if cum >= need:
                    p99 = lat
                    break
        return dict(ops_per_sec=round(submitted / dt, 1),
                    seconds=round(dt, 4), committed=submitted,
                    p99_s=round(p99, 6))

    out = {v: dict(ops_per_sec=0.0) for v in variants + ["governed"]}
    for _ in range(repeats):
        for v in variants + ["governed"]:
            row = run_round(v)
            if row["ops_per_sec"] > out[v]["ops_per_sec"]:
                out[v] = row
    best_v = max(variants, key=lambda v: out[v]["ops_per_sec"])
    gov_row, best = out["governed"], out[best_v]
    c, gov = clusters["governed"]
    events = [e.as_dict() for e in c.obs.trace.events()
              if e.kind.startswith("governor")]
    return dict(
        trace=trace_shape, seed=seed, ticks=ticks,
        entries=total_entries,
        governed=gov_row, best_static=dict(variant=best_v, **best),
        all_static={v: out[v] for v in variants},
        governor=gov.status(),
        governor_events=events,
        governor_speedup=round(
            gov_row["ops_per_sec"]
            / max(best["ops_per_sec"], 1e-9), 3),
        governor_p99_ratio=round(
            gov_row["p99_s"] / max(best["p99_s"], 1e-9), 3))


def measure_audit_overhead(cfg=None, **kw):
    """A/B the compiled-step digest chain (``audit=``); the proof is
    the ON cluster's ledger summary — the workload ran digest-checked
    (the <5% acceptance target for the ``--audit`` bench row)."""
    def proof(on_c, out):
        out["audit"] = on_c.auditor.summary()
    return _measure_flag_overhead("audit", proof, cfg, **kw)


def measure_telemetry_overhead(cfg=None, **kw):
    """A/B the compiled-step device-counter vector (``telemetry=``);
    the proof is the ON cluster's device-counter totals — the counters
    flowed (the <5% acceptance target for the ``--telemetry`` bench
    row)."""
    def proof(on_c, out):
        from rdma_paxos_tpu.obs import device as device_mod
        out["device_counters"] = {
            name: [int(v) for v in
                   on_c.device_counters[:, device_mod.INDEX[name]]]
            for name in device_mod.NAMES}
    return _measure_flag_overhead("telemetry", proof, cfg, **kw)


def measure_export_overhead(cfg=None, *, sample_period_s=0.25,
                            scrape_period_s=0.5, **kw):
    """A/B the whole ops-plane host addition (the <2% acceptance
    target): the ON variant samples the registry into a
    TimeSeriesStore + evaluates the full default rule set (burn-rate
    SLO rules included) on the drivers' 0.25 s alert cadence AND
    answers a live ``/metrics`` scrape every ``scrape_period_s`` —
    the production configuration, measured wall-cadenced exactly as
    the drivers run it. The OFF variant is the bare cluster.
    Alternating best-of rounds, the shared methodology."""
    import time as _time
    import urllib.request

    from rdma_paxos_tpu.obs import Observability
    from rdma_paxos_tpu.obs.alerts import AlertEngine, default_rules
    from rdma_paxos_tpu.obs.export import OpsExporter
    from rdma_paxos_tpu.obs.series import TimeSeriesStore
    from rdma_paxos_tpu.runtime.sim import SimCluster

    handles = {}

    def make(variant, cfg, n_replicas):
        c = SimCluster(cfg, n_replicas, fanout="psum")
        c.obs = Observability()
        c.run_until_elected(0)
        if variant == "on":
            store = TimeSeriesStore(capacity=256)
            eng = AlertEngine(c.obs.metrics, rules=default_rules(),
                              series=store)
            exp = OpsExporter(registry=c.obs.metrics, alerts=eng,
                              series=store,
                              health_fn=lambda: dict(ok=True)).start()
            handles[id(c)] = dict(store=store, eng=eng, exp=exp,
                                  n=0, scrapes=0,
                                  t_sample=float("-inf"),
                                  t_scrape=float("-inf"))
        return c

    def after_step(variant, c):
        h = handles.get(id(c))
        if h is None:
            return
        h["n"] += 1
        now = _time.monotonic()
        if now - h["t_sample"] >= sample_period_s:
            h["t_sample"] = now
            snap = c.obs.metrics.snapshot()
            h["store"].sample(snap, step=h["n"])
            h["eng"].evaluate(snap=snap)
        if now - h["t_scrape"] >= scrape_period_s:
            h["t_scrape"] = now
            urllib.request.urlopen(h["exp"].url + "/metrics",
                                   timeout=10).read()
            h["scrapes"] += 1

    def proof(on_c, out):
        h = handles[id(on_c)]
        out["export"] = dict(samples=h["store"].samples,
                             series=len(h["store"].names()),
                             rule_evals=h["eng"].evals,
                             scrapes=h["scrapes"])
        h["exp"].close()

    return _measure_flag_overhead("export", proof, cfg, make=make,
                                  after_step=after_step, **kw)


def measure_trace_overhead(cfg=None, *, sample_every=64, **kw):
    """A/B the causal-tracing plane end to end (the <2% acceptance
    target): identical closed-loop workloads where every step ALSO
    issues one stamped client-session put (the path that begins
    spans), with span sampling at the production default (ON,
    ``sample_every`` + a TraceContext attached) vs disabled (OFF,
    ``sample_every=0`` — the one switch that silences spans AND
    subsystem traces). Alternating best-of rounds, the shared
    methodology; the ON row carries the span/trace counts as proof
    that tracing actually ran."""
    from rdma_paxos_tpu.models.replicated_kvs import (ClientSession,
                                                      ReplicatedKVS)
    from rdma_paxos_tpu.obs import Observability
    from rdma_paxos_tpu.obs.spans import SpanRecorder
    from rdma_paxos_tpu.runtime.sim import SimCluster

    sessions = {}

    def make(variant, mcfg, n_replicas):
        c = SimCluster(mcfg, n_replicas, fanout="psum")
        c.obs = Observability(span_recorder=SpanRecorder(
            sample_every=(sample_every if variant == "on" else 0)))
        c.run_until_elected(0)
        sessions[id(c)] = ClientSession(ReplicatedKVS(c), client_id=7)
        return c

    def after_step(variant, c):
        s = sessions[id(c)]
        s.put(0, b"tk%03d" % (s.req_id % 512), b"v")
        # the drivers' ack-release tail (a no-op with sampling off):
        # retires acked spans so steady-state open_count stays
        # bounded, exactly as production runs it
        c.obs.spans.ack_release(0, s.req_id - 1)

    def proof(on_c, out):
        out["trace"] = dict(sample_every=sample_every,
                            spans=on_c.obs.spans.counts(),
                            traces=on_c.obs.tracectx.counts())

    return _measure_flag_overhead("trace", proof, cfg, make=make,
                                  after_step=after_step, **kw)


def measure_repair(cfg=None, *, n_replicas=3, steps=300, per_step=8,
                   payload=64, warmup=10, repeats=3,
                   corrupt_after=40, probation=6, mttr_budget=400):
    """The self-healing bench pair (``--repair``):

    * ``repair_overhead_pct`` — identical closed-loop workload through
      an audited cluster WITHOUT vs WITH a ``RepairController``
      attached (clean run: the controller's per-step findings scan is
      the overhead), ALTERNATING best-of rounds — the PR 5 audit A/B
      methodology.
    * ``mttr_steps`` — a scripted single-bit corruption of a
      follower's committed slot, then the full
      detect → quarantine → digest-verified re-install → backfill →
      re-admit loop, measured in PROTOCOL STEPS from the corrupting
      step to re-admission (step-domain: deterministic, host-load
      independent).
    """
    from rdma_paxos_tpu.chaos.faults import corrupt_slot
    from rdma_paxos_tpu.config import LogConfig
    from rdma_paxos_tpu.runtime.repair import RepairController
    from rdma_paxos_tpu.runtime.sim import SimCluster

    if cfg is None:
        cfg = LogConfig(n_slots=512, slot_bytes=128, window_slots=64,
                        batch_slots=16)
    blob = b"x" * payload
    ctls = {}

    # A/B rides the SHARED harness — only construction (controller
    # attached; fanout="gather" because quarantine isolation is a
    # peer-mask cut) and the per-step controller tick differ
    def make(variant, mcfg, n_rep):
        c = SimCluster(mcfg, n_rep, fanout="gather", audit=True)
        c.run_until_elected(0)
        if variant == "on":
            ctls[variant] = RepairController(
                c, probation_steps=probation)
        return c

    def after_step(variant, c):
        ctl = ctls.get(variant)
        if ctl is not None:
            ctl.observe()
            if ctl.needs_drain():
                ctl.drive()

    out = _measure_flag_overhead(
        "repair", lambda on_c, o: None, cfg, n_replicas=n_replicas,
        steps=steps, per_step=per_step, payload=payload,
        warmup=warmup, repeats=repeats, make=make,
        after_step=after_step)

    # --- MTTR round: scripted corruption, loop until re-admitted ---
    c = make("mttr", cfg, n_replicas)
    ctl = RepairController(c, probation_steps=probation)
    for _ in range(corrupt_after):
        c.submit(0, blob)
        c.step()
        ctl.observe()
    victim = 2
    target = int(c.last["commit"].min()) - 1
    corrupt_slot(c, victim, target)
    corrupt_step = c.step_index
    detected = quarantined = readmitted = None
    for _ in range(mttr_budget):
        c.submit(0, blob)
        c.step()
        ctl.observe()
        if detected is None and c.auditor.findings:
            detected = c.step_index
        if quarantined is None and ctl.states:
            quarantined = c.step_index
        if ctl.needs_drain():
            ctl.drive()
        if quarantined is not None and not ctl.states:
            readmitted = c.step_index
            break
    out["mttr"] = dict(
        corrupt_step=corrupt_step, detected_step=detected,
        quarantined_step=quarantined, readmitted_step=readmitted,
        mttr_steps=(readmitted - corrupt_step
                    if readmitted is not None else None),
        detection_steps=(detected - corrupt_step
                         if detected is not None else None),
        repairs_done=ctl.repairs_done,
        donors_rejected=ctl.donors_rejected,
        backfilled=c.auditor.backfilled,
        coverage_ok=(c.auditor.coverage(
            0, c.auditor.repairs[0]["lo"],
            c.auditor.repairs[0]["hi"])["ok"]
            if c.auditor.repairs else False),
        probation_steps=probation)
    return out


def measure_read_mix(read_ratio=0.9, cfg=None, *, n_replicas=3,
                     n_ops=3000, n_keys=32, repeats=3, seed=11,
                     payload=24):
    """The read-scaling A/B (``--read-ratio``): drive the IDENTICAL
    seeded read/write mix through two same-geometry clusters —

    * ``lease``  — reads served host-side by the leaseholder
      (``runtime/reads.py``): zero log traffic, batched local table
      lookups (``get_many``), writes ride the ring as usual;
    * ``log``    — the pre-lease baseline: every read rides the
      replicated log as a stamped ``OP_GET`` entry (appended,
      quorum-acked, committed, folded), competing with writes for
      ring slots and committed-ops bandwidth.

    Rounds ALTERNATE and each variant scores its fastest (the PR 5/6
    best-of methodology). The proof carried by the row: the lease
    variant's ``reads_served_total{path=lease}`` accounts for every
    read it claims, and both variants completed the same op mix."""
    import random as _random
    import time as _t

    from rdma_paxos_tpu.config import LogConfig
    from rdma_paxos_tpu.models.replicated_kvs import ReplicatedKVS
    from rdma_paxos_tpu.obs import Observability
    from rdma_paxos_tpu.runtime import reads as reads_mod
    from rdma_paxos_tpu.runtime.reads import count_read
    from rdma_paxos_tpu.runtime.sim import SimCluster

    if cfg is None:
        cfg = LogConfig(n_slots=512, slot_bytes=128, window_slots=64,
                        batch_slots=16)
    keys = [b"rk%d" % i for i in range(n_keys)]
    blob = b"x" * payload
    B = cfg.batch_slots
    CID = 5
    setups = {}
    for variant in ("log", "lease"):
        c = SimCluster(cfg, n_replicas, fanout="psum")
        c.obs = Observability()
        if variant == "lease":
            reads_mod.attach(c)
        c.run_until_elected(0)
        kv = ReplicatedKVS(c, cap=4096)
        # seed the keyspace so every GET hits a live value
        for i, k in enumerate(keys):
            kv.put(0, k, b"seed", client_id=CID, req_id=i + 1)
        while kv.last_req[0].get(CID, 0) < n_keys:
            c.step()
            kv._fold(0)
        # compile the batched-GET tiers outside the timed rounds (a
        # first-use JIT pause inside a round is not read cost)
        for t in (16, 64, 256, 512):
            kv.get_many(0, (keys * (t // n_keys + 1))[:t])
        setups[variant] = dict(c=c, kv=kv,
                               req=n_keys)   # stamped-req high water

    def run_round(variant, rep):
        c, kv = setups[variant]["c"], setups[variant]["kv"]
        rng = _random.Random(f"readmix:{seed}:{rep}")
        ops = [("r" if rng.random() < read_ratio else "w",
                rng.randrange(n_keys)) for _ in range(n_ops)]
        total_r = sum(1 for k, _ in ops if k == "r")
        total_w = n_ops - total_r
        req = setups[variant]["req"]
        pend_w: set = set()
        pend_r: dict = {}
        lease_batch: list = []
        reads_done = writes_done = 0
        steps = 0
        i = 0
        t0 = _t.perf_counter()
        while reads_done < total_r or writes_done < total_w:
            budget = B
            while i < len(ops) and budget > 0:
                kind, ki = ops[i]
                if kind == "w":
                    req += 1
                    kv.put(0, keys[ki], blob, client_id=CID,
                           req_id=req)
                    pend_w.add(req)
                    budget -= 1
                elif variant == "log":
                    req += 1
                    kv.submit_get(0, keys[ki], client_id=CID,
                                  req_id=req)
                    pend_r[req] = ki
                    budget -= 1
                else:
                    lease_batch.append(keys[ki])    # host-side: free
                i += 1
            if lease_batch:
                lm = c.leases
                assert lm is not None and lm.valid(0, 0), \
                    "leaseholder lost its lease mid-bench"
                kv.get_many(0, lease_batch)
                count_read(c.obs, "lease", 0, n=len(lease_batch))
                reads_done += len(lease_batch)
                lease_batch = []
            if writes_done < total_w or (variant == "log"
                                         and reads_done < total_r):
                c.step()
                steps += 1
                kv._fold(0)
                mark = kv.last_req[0].get(CID, 0)
                done_w = [q for q in pend_w if q <= mark]
                for q in done_w:
                    pend_w.discard(q)
                writes_done += len(done_w)
                done_r = [q for q in pend_r if q <= mark]
                if done_r:
                    kv.get_many(0, [keys[pend_r.pop(q)]
                                    for q in done_r])
                    count_read(c.obs, "log", 0, n=len(done_r))
                    reads_done += len(done_r)
        dt = _t.perf_counter() - t0
        setups[variant]["req"] = req
        return dict(seconds=round(dt, 4), steps=steps,
                    reads=reads_done, writes=writes_done,
                    read_ops_per_sec=round(reads_done / dt, 1),
                    write_ops_per_sec=round(writes_done / dt, 1),
                    total_ops_per_sec=round(n_ops / dt, 1))

    best = {v: None for v in setups}
    for rep in range(repeats):
        for variant in ("log", "lease"):
            r = run_round(variant, rep)
            if best[variant] is None or (r["read_ops_per_sec"]
                                         > best[variant]
                                         ["read_ops_per_sec"]):
                best[variant] = r
    from rdma_paxos_tpu.runtime.reads import read_counts
    out = dict(read_ratio=read_ratio, n_ops=n_ops, repeats=repeats,
               lease=best["lease"], log=best["log"],
               lease_read_speedup=round(
                   best["lease"]["read_ops_per_sec"]
                   / max(best["log"]["read_ops_per_sec"], 1e-9), 2),
               accounting=dict(
                   lease_variant=read_counts(setups["lease"]["c"].obs),
                   log_variant=read_counts(setups["log"]["c"].obs)),
               leases=setups["lease"]["c"].leases.status())
    return out


def measure_watch_mix(watch_ratio=0.5, cfg=None, *, n_replicas=3,
                      n_ops=2000, n_keys=32, n_watchers=4,
                      repeats=3, seed=11, payload=24,
                      cdc_dir=None):
    """The streams fan-out A/B (``--watch-ratio``): drive the
    IDENTICAL seeded write workload through two same-geometry
    clusters —

    * ``plain``    — no streams hub (the bare engine);
    * ``attached`` — the streams hub attached with ``n_watchers``
      subscribers each watching the first ``watch_ratio`` of the
      keyspace, plus a CDC JSONL sink, drained concurrently.

    Rounds ALTERNATE and each variant scores its fastest committed
    write throughput (the PR 5/6 best-of methodology). The row's
    claim: the whole streams surface — tail snapshots, pump decode,
    fan-out, CDC export — costs <3% committed-write throughput (it
    never enters the dispatch path; the engine only kicks a condition
    variable), while ``watch_fanout_events_per_sec`` reports the
    delivery rate and ``cdc_lag_entries`` the sink's distance from
    the committed frontier after the end-of-round flush (0 = the
    exporter kept up)."""
    import os as _os
    import random as _random
    import tempfile as _tempfile
    import time as _t

    from rdma_paxos_tpu.config import LogConfig
    from rdma_paxos_tpu.models.replicated_kvs import ReplicatedKVS
    from rdma_paxos_tpu.obs import Observability
    from rdma_paxos_tpu.runtime.sim import SimCluster
    from rdma_paxos_tpu import streams as streams_mod

    if cfg is None:
        cfg = LogConfig(n_slots=512, slot_bytes=128, window_slots=64,
                        batch_slots=16)
    keys = [b"wk%02d" % i for i in range(n_keys)]
    cut = max(1, min(n_keys, round(watch_ratio * n_keys)))
    blob = b"x" * payload
    B = cfg.batch_slots
    CID = 6
    if cdc_dir is None:
        cdc_dir = _tempfile.mkdtemp(prefix="watchmix")
    setups = {}
    for variant in ("plain", "attached"):
        c = SimCluster(cfg, n_replicas, fanout="psum")
        c.obs = Observability()
        entry = dict(c=c, req=0, subs=(), hub=None)
        if variant == "attached":
            hub = streams_mod.attach(
                c, cdc_path=_os.path.join(cdc_dir, "cdc.jsonl"))
            entry["hub"] = hub
            entry["subs"] = [
                hub.subscribe(0, lo=keys[0],
                              hi=None if cut >= n_keys else keys[cut])
                for _ in range(n_watchers)]
        c.run_until_elected(0)
        entry["kv"] = ReplicatedKVS(c, cap=4096)
        setups[variant] = entry

    def run_round(variant, rep):
        ent = setups[variant]
        c, kv, subs = ent["c"], ent["kv"], ent["subs"]
        rng = _random.Random(f"watchmix:{seed}:{rep}")
        order = [rng.randrange(n_keys) for _ in range(n_ops)]
        req = ent["req"]
        pend: set = set()
        done = steps = events = 0
        i = 0
        t0 = _t.perf_counter()
        while done < n_ops:
            budget = B
            while i < len(order) and budget > 0:
                req += 1
                kv.put(0, keys[order[i]], blob, client_id=CID,
                       req_id=req)
                pend.add(req)
                i += 1
                budget -= 1
            c.step()
            steps += 1
            kv._fold(0)
            mark = kv.last_req[0].get(CID, 0)
            done_now = [q for q in pend if q <= mark]
            for q in done_now:
                pend.discard(q)
            done += len(done_now)
            for s in subs:
                events += len(s.poll(max_n=1024))
        dt = _t.perf_counter() - t0
        ent["req"] = req
        hub = ent["hub"]
        lag = 0
        if hub is not None:
            # flush: the pump drains asynchronously — wait it out so
            # the fan-out count covers every committed write and the
            # reported CDC lag is the exporter's true residue
            target = hub.tails[0].length()
            deadline = _t.monotonic() + 10
            while (hub.watch.cursors().get(0, 0) < target
                   and _t.monotonic() < deadline):
                _t.sleep(0.002)
            for s in subs:
                events += len(s.poll(max_n=1 << 16))
            lag = max(0, target - hub.watch.cursors().get(0, 0))
        dt_total = _t.perf_counter() - t0
        return dict(seconds=round(dt, 4), steps=steps, writes=done,
                    write_ops_per_sec=round(done / dt, 1),
                    events=events,
                    watch_fanout_events_per_sec=round(
                        events / dt_total, 1),
                    cdc_lag_entries=lag)

    best = {v: None for v in setups}
    for rep in range(repeats):
        for variant in ("plain", "attached"):
            r = run_round(variant, rep)
            if best[variant] is None or (r["write_ops_per_sec"]
                                         > best[variant]
                                         ["write_ops_per_sec"]):
                best[variant] = r
    hub = setups["attached"]["hub"]
    overhead = round(
        100.0 * (best["plain"]["write_ops_per_sec"]
                 - best["attached"]["write_ops_per_sec"])
        / max(best["plain"]["write_ops_per_sec"], 1e-9), 2)
    out = dict(watch_ratio=watch_ratio, n_ops=n_ops, n_keys=n_keys,
               n_watchers=n_watchers, watched_keys=cut,
               repeats=repeats, plain=best["plain"],
               attached=best["attached"],
               watch_attach_overhead_pct=overhead,
               cdc=dict(exported=hub.cdc.exported(0),
                        lag=best["attached"]["cdc_lag_entries"]),
               watch=hub.watch.status())
    hub.fail_all("bench end")
    return out


def measure_txn(cfg=None, *, n_replicas=3, n_groups=3, n_probe=12,
                n_ops=400, n_keys=48, repeats=3, seed=17):
    """The transaction bench (``--txn``), three claims on one
    ``txn=True`` sharded geometry:

    * **dispatch-count proof** — each cross-group 2PC commit (a
      put-pair spanning two groups) is driven serially to completion
      while counting ``ShardedCluster.dispatches``: the in-dispatch
      commit lane resolves prepare votes + the commit decision in ~2
      protocol dispatches (the classic coordinator pays 2 network
      round trips PER PHASE);
    * **commit latency vs single-key** — the same probe for a plain
      stamped single-key put (1 dispatch), reported as a ratio;
    * **mergeable throughput** — seeded A/B, rounds ALTERNATING and
      each variant keeping its fastest round (the PR 5/6 best-of
      methodology): ``merge`` drives INCR transactions through the
      coordinator's fast path, ``plain`` drives the identical count
      of stamped single-key puts over the same keys; the fast path
      skips prepare entirely (one MERGE record per write), so its
      committed throughput must hold ~1x plain (target >=0.9x).
    """
    import random as _random
    import time as _t

    from rdma_paxos_tpu.config import LogConfig
    from rdma_paxos_tpu.obs import Observability
    from rdma_paxos_tpu.shard.cluster import ShardedCluster
    from rdma_paxos_tpu.shard.kvs import ShardedKVS
    from rdma_paxos_tpu.txn import attach_coordinator
    from rdma_paxos_tpu.txn.chaos import keys_for_groups

    if cfg is None:
        cfg = LogConfig(n_slots=512, slot_bytes=128, window_slots=64,
                        batch_slots=16)
    shard = ShardedCluster(cfg, n_replicas, n_groups, txn=True)
    shard.obs = Observability()
    kv = ShardedKVS(shard, cap=4096)
    attach_coordinator(kv, timeout_steps=256)
    shard.place_leaders()
    G = shard.G
    B = cfg.batch_slots
    CID = 9

    pools = keys_for_groups(kv.router, n_probe + n_keys // G + 2,
                            prefix=b"txb")

    # ---- serial probes: dispatches + wall latency per commit ----
    def probe_2pc(i):
        ga, gb = i % G, (i + 1) % G
        ka = pools[ga][i]
        kb = pools[gb][i]
        d0, t0 = shard.dispatches, _t.perf_counter()
        h = kv.transact([("put", ka, b"a%d" % i),
                         ("put", kb, b"b%d" % i)])
        steps = 0
        while not h.done and steps < 64:
            shard.step()
            steps += 1
        assert h.committed, f"probe txn aborted: {h.abort_reason}"
        return shard.dispatches - d0, _t.perf_counter() - t0, steps

    req = [0] * G
    def probe_put(i):
        g = i % G
        key = pools[g][n_probe + 1]
        req[g] += 1
        conn = kv.conn_for(CID, g)
        d0, t0 = shard.dispatches, _t.perf_counter()
        kv.put(key, b"p%d" % i, client_id=CID, req_id=req[g])
        steps = 0
        while steps < 64:
            shard.step()
            steps += 1
            kv.groups[g]._fold(shard.leader_hint(g))
            if kv.groups[g].last_req[
                    shard.leader_hint(g)].get(conn, 0) >= req[g]:
                break
        return shard.dispatches - d0, _t.perf_counter() - t0, steps

    # warmup: compile the txn-lane program + settle leaders before
    # timing (the probes report steady-state dispatch counts)
    h = kv.transact([("put", pools[0][n_probe], b"w"),
                     ("put", pools[1][n_probe], b"w")])
    for _ in range(8):
        if h.done:
            break
        shard.step()
    for g in range(G):      # first fold compiles each group's apply
        kv.put(pools[g][n_probe], b"w", client_id=CID, req_id=1)
        req[g] = 1
    shard.step()
    for g in range(G):
        kv.groups[g]._fold(shard.leader_hint(g))

    twopc = [probe_2pc(i) for i in range(n_probe)]
    single = [probe_put(i) for i in range(n_probe)]
    mean = lambda xs: sum(xs) / len(xs)
    probe = dict(
        twopc=dict(dispatches=round(mean([d for d, _, _ in twopc]), 2),
                   seconds=round(mean([s for _, s, _ in twopc]), 5),
                   steps=round(mean([st for _, _, st in twopc]), 2)),
        single=dict(dispatches=round(mean([d for d, _, _ in single]), 2),
                    seconds=round(mean([s for _, s, _ in single]), 5),
                    steps=round(mean([st for _, _, st in single]), 2)))
    probe["latency_ratio"] = round(
        probe["twopc"]["seconds"]
        / max(probe["single"]["seconds"], 1e-9), 2)

    # ---- throughput A/B: mergeable fast path vs plain puts ----
    # one op in flight per key slot (64-way closed loop); merge keys
    # and plain keys are the same set, so routing and fold cost match
    mkeys = [pools[i % G][n_probe + 2 + i // G]
             for i in range(n_keys)]
    mreq = [0] * G

    def run_round(variant, rep):
        rng = _random.Random(f"txnbench:{seed}:{rep}")
        order = [rng.randrange(n_keys) for _ in range(n_ops)]
        slot_busy = [None] * n_keys      # handle | (g, req) in flight
        i = done = steps = 0
        t0 = _t.perf_counter()
        while done < n_ops:
            budget = B
            while i < len(order) and budget > 0:
                k = order[i]
                if slot_busy[k] is not None:
                    break               # keep per-key FIFO: wait
                key = mkeys[k]
                if variant == "merge":
                    slot_busy[k] = kv.transact([("incr", key, 1)])
                else:
                    g = kv.group_of(key)
                    mreq[g] += 1
                    kv.put(key, b"v%d" % i, client_id=CID + 1,
                           req_id=mreq[g])
                    slot_busy[k] = (g, mreq[g])
                i += 1
                budget -= 1
            shard.step()
            steps += 1
            marks = {}
            for k, st in enumerate(slot_busy):
                if st is None:
                    continue
                if variant == "merge":
                    if st.done:
                        assert st.committed
                        slot_busy[k] = None
                        done += 1
                else:
                    g, q = st
                    if g not in marks:
                        lead = shard.leader_hint(g)
                        kv.groups[g]._fold(lead)
                        marks[g] = kv.groups[g].last_req[lead]
                    if marks[g].get(kv.conn_for(CID + 1, g), 0) >= q:
                        slot_busy[k] = None
                        done += 1
        dt = _t.perf_counter() - t0
        return dict(seconds=round(dt, 4), steps=steps, writes=done,
                    write_ops_per_sec=round(done / dt, 1))

    best = {"plain": None, "merge": None}
    for rep in range(repeats):
        for variant in ("plain", "merge"):
            r = run_round(variant, rep)
            if (best[variant] is None
                    or r["write_ops_per_sec"]
                    > best[variant]["write_ops_per_sec"]):
                best[variant] = r
    ratio = round(best["merge"]["write_ops_per_sec"]
                  / max(best["plain"]["write_ops_per_sec"], 1e-9), 3)
    coord = shard.txn.health()
    return dict(n_groups=G, n_probe=n_probe, n_ops=n_ops,
                n_keys=n_keys, repeats=repeats, seed=seed,
                probe=probe, plain=best["plain"],
                merge=best["merge"], merge_throughput_ratio=ratio,
                coordinator=coord)


def client_worker(port, n, lat, tid, pipeline=1, retries=5,
                  completed=None):
    """Pipelined client (the redis-benchmark -P analog): P commands per
    write — the app's read() picks them up as ONE buffer, so they ride a
    single consensus event; latency is measured per pipelined batch.
    A severed connection (a refused event during leadership churn — the
    shim fails fast with -1 and the session drops) reconnects and
    retries the batch, bounded, exactly as a real client would.
    ``completed[tid]`` tracks the commands ACKNOWLEDGED so far, so a
    worker that exhausts its retries (and dies with the exception)
    still leaves an honest count behind."""
    s = socket.create_connection(("127.0.0.1", port), timeout=30)
    f = s.makefile("rb")
    done = 0
    while done < n:
        k = min(pipeline, n - done)
        t0 = time.perf_counter()
        try:
            s.sendall(b"".join(b"SET k%d-%d v%d\n" % (tid, done + i, i)
                               for i in range(k)))
            for _ in range(k):
                if f.readline().strip() != b"+OK":
                    raise OSError("severed mid-batch")
        except OSError:
            if retries <= 0:
                raise
            retries -= 1
            try:
                s.close()
            except OSError:
                pass
            time.sleep(0.2)
            s = socket.create_connection(("127.0.0.1", port), timeout=30)
            f = s.makefile("rb")
            continue                 # re-issue the same batch
        lat.append(time.perf_counter() - t0)
        done += k
        if completed is not None:
            completed[tid] = done
    s.close()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--replicas", type=int, default=3)
    ap.add_argument("--requests", type=int, default=2000)
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--port-base", type=int, default=7600)
    ap.add_argument("--period", type=float, default=0.02)
    # log geometry (defaults = the historical run_bench shape; the
    # REDIS_r05 headline geometry is 8192/256/1024/1024)
    ap.add_argument("--n-slots", type=int, default=2048)
    ap.add_argument("--slot-bytes", type=int, default=512)
    ap.add_argument("--window-slots", type=int, default=256)
    ap.add_argument("--batch-slots", type=int, default=256)
    ap.add_argument("--pipeline", type=int, default=1,
                    help="commands per client batch (redis-benchmark -P)")
    ap.add_argument("--threaded-app", action="store_true",
                    help="run toyserver thread-per-connection (memcached"
                         "-style): each client's reads block in the shim "
                         "commit wait concurrently, exercising the "
                         "pipelined shim")
    ap.add_argument("--json", default=None,
                    help="append a JSON result line to this file")
    ap.add_argument("--metrics-json", default=None,
                    help="write the full obs metrics snapshot here "
                         "(default: <workdir>/metrics.json)")
    ap.add_argument("--trace", action="store_true",
                    help="causal tracing at 100%% sampling: every "
                         "command gets an end-to-end span; writes the "
                         "raw span dump and a Perfetto-loadable Chrome "
                         "trace next to the metrics snapshot")
    ap.add_argument("--trace-json", default=None,
                    help="Chrome trace output path (default: "
                         "<workdir>/trace.perfetto.json)")
    ap.add_argument("--groups", type=int, default=0,
                    help="sharded mode: with no e2e flags, delegate to "
                         "benchmarks/shard_bench.py (the multi-group "
                         "one-dispatch sim bench); with --e2e (or any "
                         "e2e flag) run the FULL app path against a "
                         "ShardedClusterDriver — clients spread over "
                         "all replicas, connections key-prefix-routed "
                         "onto G consensus groups")
    ap.add_argument("--e2e", action="store_true",
                    help="with --groups: force the sharded end-to-end "
                         "app path instead of the shard_bench sim sweep")
    ap.add_argument("--pipeline-depth", type=int, default=2,
                    help="driver dispatch-pipeline depth (encode batch "
                         "k+1 while batch k runs on the device; 0/1 = "
                         "fully serial loop)")
    ap.add_argument("--scan", type=int, default=0, metavar="K",
                    help="device-resident K-window scan tier: burst "
                         "dispatches run up to K fused protocol steps "
                         "and return ONE consolidated minimal readback "
                         "(scalar matrix + in-dispatch replay rows) — "
                         "the host pays one dispatch + one transfer "
                         "per K steps. K caps the fused tier "
                         "(2/4/8/16). 0 = off")
    ap.add_argument("--ab-hostpath", type=int, default=2,
                    help="with --scan: rounds per variant for the "
                         "host-path A/B (vectorized data plane + scan "
                         "tier ON vs scalar reference loops + scan "
                         "OFF; alternating best-of); emits the "
                         "host_path_speedup row with per-phase us "
                         "attribution. 0 disables")
    ap.add_argument("--ab-pipeline", type=int, default=2,
                    help="rounds per variant for the pipeline on/off "
                         "A/B (alternating best-of, the --audit "
                         "methodology); emits a pipeline_speedup row. "
                         "0 disables")
    ap.add_argument("--fence", action="store_true",
                    help="fence each device step with block_until_ready "
                         "so step-phase histograms attribute device-sync "
                         "time separately from dispatch (profiling mode; "
                         "serializes the dispatch pipeline)")
    ap.add_argument("--audit", action="store_true",
                    help="silent-divergence auditing: compile the "
                         "digest-chain step variants, run the cluster "
                         "audit ledger + flight recorder + SLO alerts "
                         "during the workload, and emit an "
                         "audit-overhead A/B row (digests on vs off)")
    ap.add_argument("--repair", action="store_true",
                    help="self-healing bench: after the e2e run, A/B "
                         "an audited cluster with vs without the "
                         "RepairController attached "
                         "(repair_overhead_pct, alternating best-of) "
                         "and measure the full corruption→quarantine→"
                         "verified-reinstall→backfill→re-admit loop "
                         "in protocol steps (mttr_steps)")
    ap.add_argument("--read-ratio", type=float, default=0.0,
                    help="read-mix workload: after the e2e run, A/B "
                         "the read-scaling paths at this read "
                         "fraction (e.g. 0.9 = 10:1 read-heavy) — "
                         "leader-lease host-side serving vs the "
                         "reads-through-log baseline on the same "
                         "core; emits read_ops_per_sec / "
                         "write_ops_per_sec / lease_read_speedup "
                         "rows with path accounting")
    ap.add_argument("--watch-ratio", type=float, default=0.0,
                    help="streams fan-out workload: after the e2e "
                         "run, A/B the identical seeded write mix "
                         "with vs without the streams hub attached "
                         "(watchers covering this keyspace fraction "
                         "plus a CDC sink) — emits "
                         "watch_fanout_events_per_sec / "
                         "cdc_lag_entries and a "
                         "watch_attach_overhead_pct row (target <3%%)")
    ap.add_argument("--txn", action="store_true",
                    help="transaction bench: serial dispatch-count "
                         "probes proving a cross-group 2PC commit "
                         "resolves in ~2 dispatches (vs 1 for a "
                         "single-key put), plus a seeded alternating "
                         "best-of A/B of mergeable INCR transactions "
                         "vs plain single-key puts — emits "
                         "txn_commit_dispatches / "
                         "txn_commit_latency_ratio / "
                         "txn_merge_throughput_ratio rows "
                         "(target >=0.9x)")
    ap.add_argument("--telemetry", action="store_true",
                    help="device telemetry: compile the counter-vector "
                         "step variants (obs/device.py), export "
                         "device_*{replica=} series during the "
                         "workload, and emit a telemetry_overhead_pct "
                         "A/B row (counters on vs off, target <5%%)")
    ap.add_argument("--trace-overhead", action="store_true",
                    help="A/B the causal-tracing plane: span sampling "
                         "at the production default + TraceContext vs "
                         "sampling disabled, identical stamped-session "
                         "workloads — emits a trace_overhead_pct row "
                         "(target <2%%)")
    ap.add_argument("--profile", action="store_true",
                    help="bounded jax.profiler capture of the client "
                         "wave; writes the raw capture, a "
                         "program_report.json (per-variant flops / "
                         "bytes / memory), and — with --trace — ONE "
                         "merged Perfetto timeline: client spans + "
                         "host phases + device execution on shared "
                         "clock anchors")
    ap.add_argument("--profile-secs", type=float, default=60.0,
                    help="hard bound on the --profile capture")
    ap.add_argument("--governor", action="store_true",
                    help="adaptive-dispatch A/B (standalone — no e2e "
                         "stack): replay seeded arrival traces "
                         "(bursty/diurnal/step) through the governed "
                         "engine vs every static geometry, emitting "
                         "governor_speedup (>= 1.15x target on the "
                         "bursty trace) and governor_p99_ratio "
                         "(<= 1.1: latency never traded away) rows")
    ap.add_argument("--governor-ticks", type=int, default=400,
                    help="trace length in ticks (CI smoke uses a "
                         "small value)")
    ap.add_argument("--governor-shapes", default="bursty,diurnal,step",
                    help="comma-separated trace shapes to run")
    ap.add_argument("--governor-seed", type=int, default=0)
    ap.add_argument("--governor-repeats", type=int, default=3)
    ap.add_argument("--governor-trace", default=None, metavar="PATH",
                    help="write the governed runs' decision trace "
                         "(governor_* events) as JSON — the CI "
                         "failure artifact")
    ap.add_argument("--serve-metrics", nargs="?", const=0,
                    default=None, type=int, metavar="PORT",
                    help="serve the live ops endpoints (/metrics "
                         "/healthz /series /alerts) on this localhost "
                         "port for the whole run (no value = "
                         "ephemeral) — watch a long bench with the "
                         "fleet console or any Prometheus scraper; "
                         "also emits the export_overhead_pct A/B row "
                         "(series+rules+scrape on vs off, target "
                         "<2%%)")
    args = ap.parse_args()

    sharded_e2e = bool(args.groups) and (
        args.e2e or args.fence or args.audit or args.metrics_json
        or args.threaded_app or args.trace or args.trace_json
        or args.telemetry or args.profile
        or args.serve_metrics is not None)
    if args.groups and not sharded_e2e:
        # plain --groups N: the sharded SIM sweep (shard_bench owns its
        # own cluster lifecycle). Any e2e flag routes to the sharded
        # end-to-end path below instead.
        from benchmarks.shard_bench import main as shard_main
        fwd = ["--groups", str(args.groups),
               "--replicas", str(args.replicas)]
        if args.json:
            fwd += ["--json", args.json]
        return shard_main(fwd)
    os.environ.setdefault(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=8")
    import jax
    if os.environ.get("RP_BENCH_CPU", "1") == "1":
        jax.config.update("jax_platforms", "cpu")
    from rdma_paxos_tpu.utils.compile_cache import use_compile_cache
    use_compile_cache()

    if args.governor:
        # standalone mode (like plain --groups): the governor A/B is
        # an engine-closed-loop measurement — no app/proxy stack
        import json as _json

        from benchmarks.reporting import emit
        all_events = {}
        speedups = {}
        for shape in [s.strip() for s in
                      args.governor_shapes.split(",") if s.strip()]:
            gv = measure_governor(shape, ticks=args.governor_ticks,
                                  seed=args.governor_seed,
                                  repeats=args.governor_repeats)
            best = gv["best_static"]
            print(f"governor [{shape}]: "
                  f"{gv['governed']['ops_per_sec']} ops/s governed vs "
                  f"{best['ops_per_sec']} ops/s best static "
                  f"({best['variant']}) -> {gv['governor_speedup']}x, "
                  f"p99 {gv['governed']['p99_s'] * 1e3:.2f}ms vs "
                  f"{best['p99_s'] * 1e3:.2f}ms "
                  f"({gv['governor_p99_ratio']}x)")
            detail = {k: v for k, v in gv.items()
                      if k != "governor_events"}
            emit("governor_speedup", gv["governor_speedup"], "x",
                 detail=detail, json_path=args.json)
            emit("governor_p99_ratio", gv["governor_p99_ratio"], "x",
                 detail=dict(trace=shape,
                             governed_p99_s=gv["governed"]["p99_s"],
                             best_static_p99_s=best["p99_s"]),
                 json_path=args.json)
            all_events[shape] = gv["governor_events"]
            speedups[shape] = gv["governor_speedup"]
        if args.governor_trace:
            with open(args.governor_trace, "w") as f:
                _json.dump(dict(ticks=args.governor_ticks,
                                seed=args.governor_seed,
                                speedups=speedups,
                                events=all_events), f, indent=2)
            print(f"governor decision trace: {args.governor_trace}")
        return

    from rdma_paxos_tpu.config import LogConfig, TimeoutConfig
    from rdma_paxos_tpu.runtime.driver import ClusterDriver

    cfg = LogConfig(n_slots=args.n_slots, slot_bytes=args.slot_bytes,
                    window_slots=args.window_slots,
                    batch_slots=args.batch_slots)
    ports = [args.port_base + i for i in range(args.replicas)]
    wd = tempfile.mkdtemp(prefix="rp_bench_")
    subprocess.run(["make", "-C", NATIVE], check=True, capture_output=True)

    tcfg = TimeoutConfig(elec_timeout_low=0.5, elec_timeout_high=1.0)
    if args.profile:
        # the profiler multiplies host + dispatch cost on a shared
        # box; a 0.5 s election timer reads that as a dead leader and
        # churns mid-capture — widen so the capture observes SERVING,
        # not election storms (boot takes a few seconds longer)
        tcfg = TimeoutConfig(elec_timeout_low=5.0,
                             elec_timeout_high=8.0)
    if sharded_e2e:
        from rdma_paxos_tpu.runtime.sharded_driver import (
            ShardedClusterDriver)
        driver = ShardedClusterDriver(
            cfg, args.replicas, args.groups, workdir=wd,
            app_ports=ports, timeout_cfg=tcfg, fanout="psum",
            fence=args.fence, audit=args.audit,
            telemetry=args.telemetry, pipeline=args.pipeline_depth,
            scan=bool(args.scan))
    else:
        driver = ClusterDriver(
            cfg, args.replicas, workdir=wd, app_ports=ports,
            timeout_cfg=tcfg, fanout="psum", fence=args.fence,
            audit=args.audit, telemetry=args.telemetry,
            pipeline=args.pipeline_depth, scan=bool(args.scan))
    if args.scan:
        from rdma_paxos_tpu.runtime.sim import cap_scan_tiers
        try:
            cap_scan_tiers(driver.cluster, args.scan)
        except ValueError as e:
            raise SystemExit(f"--scan: {e}")
    if args.trace:
        # 100% sampling (the default is rate-limited); capacity sized
        # so a full run's spans are retained for the export
        driver.obs.spans.resize(max(args.requests * 2, 4096))
        driver.obs.spans.set_sample_every(1)
    if args.serve_metrics is not None:
        exp = driver.serve_metrics(args.serve_metrics)
        print(f"ops endpoints: {exp.url}/metrics  /healthz  /series  "
              f"/alerts  (fleet console: python -m "
              f"rdma_paxos_tpu.obs.console --scrape {exp.url})")
    print("prewarming step/burst compiles...")
    driver.prewarm()
    apps = []
    for r, port in enumerate(ports):
        env = dict(os.environ)
        env["LD_PRELOAD"] = os.path.join(NATIVE, "interpose.so")
        env["RP_PROXY_SOCK"] = os.path.join(wd, f"proxy{r}.sock")
        cmd = [os.path.join(NATIVE, "toyserver"), str(port)]
        if args.threaded_app:
            cmd.append("-t")
        apps.append(subprocess.Popen(cmd, env=env,
                                     stderr=subprocess.DEVNULL))
    time.sleep(0.3)
    driver.run(period=args.period)
    t0 = time.time()
    while driver.leader() < 0:
        time.sleep(0.05)
        if time.time() - t0 > 120:
            raise SystemExit("no leader elected")
    lead = driver.leader()
    if sharded_e2e:
        print(f"all {args.groups} groups led: {driver.leaders()} "
              f"(in {time.time() - t0:.1f}s)")
    else:
        print(f"leader: replica {lead} "
              f"(elected in {time.time() - t0:.1f}s)")

    def port_for(tid: int) -> int:
        # sharded: every replica is a serving front-end — spread the
        # clients; each client tid keys k<tid>-..., so a connection's
        # whole keyspace shares one routing prefix (the client contract)
        if sharded_e2e:
            return ports[tid % args.replicas]
        return ports[lead]

    def run_wave(total: int):
        """One full client wave; returns (ops/s, seconds, sorted
        latencies, commands completed) — the rate counts commands
        ACKNOWLEDGED, not commands asked for."""
        per_w = total // args.clients
        lats_w = [[] for _ in range(args.clients)]
        completed = [0] * args.clients
        threads = [threading.Thread(target=client_worker,
                                    args=(port_for(i), per_w, lats_w[i],
                                          i, args.pipeline),
                                    kwargs=dict(completed=completed))
                   for i in range(args.clients)]
        t0_w = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        dt_w = time.perf_counter() - t0_w
        flat: list = []
        for l in lats_w:
            flat.extend(l)
        flat.sort()
        return sum(completed) / dt_w, dt_w, flat, sum(completed)

    def die(msg: str):
        """A dead wave is a failed run: stop what we started, exit 1."""
        driver.stop()
        for a in apps:
            a.kill()
            a.wait()
        raise SystemExit(msg)

    def wave_ops() -> float:
        """ops/s of one full wave for the A/B rounds; a wave in which
        any client exhausted its retries fails the run instead of
        scoring a rate."""
        ops_w, _dt, _lat, got = run_wave(args.requests)
        if got < args.requests // args.clients * args.clients:
            die(f"A/B wave died: {got} commands completed")
        return ops_w

    profile_session = None
    if args.profile:
        # host-phase slices feed the merged timeline's middle track;
        # the device capture is bounded (the poll loop enforces it)
        driver._phase_prof.enable_events()
        profile_session = driver.start_profile(
            seconds=args.profile_secs,
            log_dir=os.path.join(wd, "profile"))
    ops, dt, lat, completed = run_wave(args.requests)
    if profile_session is not None:
        driver.stop_profile()
    nb = len(lat)
    n = args.requests // args.clients * args.clients
    print(f"committed SETs: {completed} of {n} in {dt:.2f}s -> "
          f"{ops:.0f} ops/s "
          f"({args.clients} clients, pipeline {args.pipeline}, "
          f"dispatch depth {args.pipeline_depth}"
          f"{', %d groups' % args.groups if sharded_e2e else ''}"
          f"{', threaded app' if args.threaded_app else ''})")
    if nb:
        print(f"per-batch latency p50={lat[nb // 2] * 1e3:.2f}ms "
              f"p95={lat[int(nb * .95)] * 1e3:.2f}ms "
              f"p99={lat[int(nb * .99)] * 1e3:.2f}ms")
    else:
        print("per-batch latency: no completed batches")

    # observability export: the registry snapshot (commit-latency
    # histogram buckets, per-replica role/term gauges, rebase-headroom
    # gauge, proxy/replay counters) rides alongside the wall-clock
    # numbers so BENCH_* rounds carry protocol-level detail, and the
    # aggregated health view prints for the operator
    import json
    metrics_snap = driver.obs.metrics.snapshot()
    metrics_path = args.metrics_json or os.path.join(wd, "metrics.json")
    driver.obs.metrics.write_json(metrics_path)
    health = driver.health()
    print(f"metrics snapshot: {metrics_path} "
          f"({len(metrics_snap['counters'])} counters, "
          f"{len(metrics_snap['gauges'])} gauges, "
          f"{len(metrics_snap['histograms'])} histograms)")
    print("METRICS:" + json.dumps(metrics_snap))
    print("HEALTH:" + json.dumps(health))
    if completed < n:
        # a client exhausted its retries: the export above is the
        # post-mortem such a run needs, and the run itself has failed
        die(f"workload died: {completed} of {n} SETs completed")

    trace_detail = None
    if args.trace:
        # let the followers' commit/apply frontiers catch up so every
        # span carries all R replicas' marks before the export
        time.sleep(0.5)
        from rdma_paxos_tpu.obs import spans as spans_mod
        # ONE dump feeds both artifacts + the stats, so the on-disk
        # spans.json and the Perfetto trace can never disagree
        raw = driver.obs.spans.dump()
        spans_path = os.path.join(wd, "spans.json")
        with open(spans_path, "w") as sf:
            json.dump(raw, sf, indent=2)
        trace_path = (args.trace_json
                      or os.path.join(wd, "trace.perfetto.json"))
        with open(trace_path, "w") as tf:
            json.dump(spans_mod.to_chrome_trace(
                raw, max_cp_tracks=4096), tf)
        done = [s for s in raw["spans"] if s["status"] == "done"]
        corr = [s for s in done
                if s["term"] is not None
                and len({r for p, r, _ in s["events"]
                         if p == "commit"}) >= args.replicas]
        # denominator: every event the proxy layer SUBMITTED (counted
        # at intake; a few may have failed rather than committed)
        submitted = sum(
            v for k, v in metrics_snap["counters"].items()
            if k.startswith("proxy_events_total"))
        cover = len(done) / max(submitted, 1)
        trace_detail = dict(
            spans=len(raw["spans"]), completed=len(done),
            correlated_all_replicas=len(corr),
            submitted_events=submitted,
            coverage=round(cover, 4), dropped=raw["dropped"],
            spans_json=spans_path, perfetto_json=trace_path)
        print(f"spans: {len(raw['spans'])} sampled, {len(done)} "
              f"completed, {len(corr)} correlated across all "
              f"{args.replicas} replicas ({cover:.1%} of {submitted} "
              f"submitted events) -> {trace_path} (load in "
              f"https://ui.perfetto.dev)")
        print(spans_mod.format_breakdown(spans_mod.breakdown(raw)))

    from benchmarks.reporting import emit

    def phase_sums():
        """Per-phase StepPhaseProfiler sums — zero-sample phases
        suppressed (a fence-off run must not carry a dead
        device_sync column)."""
        return driver._phase_prof.sums()

    profile_detail = None
    if args.profile:
        from rdma_paxos_tpu.obs import device as device_mod

        # per-STEP_CACHE-variant compiled-program cost report: what
        # one dispatch COSTS, next to what it DID (the counters)
        report = device_mod.write_program_report(
            os.path.join(wd, "program_report.json"), driver.cluster,
            tiers=(2,))
        emit("program_report", len(report["variants"]), "variants",
             detail=dict(
                 path=report["path"], backend=report["backend"],
                 engine=report["engine"],
                 variants=[{k: v for k, v in row.items()
                            if k in ("variant", "flops",
                                     "bytes_accessed")}
                           for row in report["variants"]]),
             obs=driver.obs, json_path=args.json)
        merged_path = os.path.join(wd, "merged.perfetto.json")
        # the SAME dump that fed spans.json / trace.perfetto.json —
        # a second dump() here would capture spans that completed in
        # between and the three artifacts would disagree
        span_dumps = [raw] if args.trace else []
        merged = device_mod.merge_timeline(
            span_dumps,
            phase_events=list(driver._phase_prof.events or []),
            profiler=profile_session, max_cp_tracks=4096)
        with open(merged_path, "w") as mf:
            json.dump(merged, mf)
        profile_detail = dict(
            merged_perfetto=merged_path,
            profile_dir=profile_session.log_dir,
            device_events=merged["otherData"]["device_events"],
            device_events_dropped=merged["otherData"][
                "device_events_dropped"],
            host_phase_events=merged["otherData"]["host_phase_events"],
            span_tracks=merged["otherData"]["spans"],
            program_report=report["path"])
        print(f"profile: {profile_detail['device_events']} device "
              f"events ({profile_detail['device_events_dropped']} "
              f"dropped past the cap) + "
              f"{profile_detail['host_phase_events']} host-phase "
              f"slices + {profile_detail['span_tracks']} spans -> "
              f"{merged_path} (one timeline — load in "
              f"https://ui.perfetto.dev)")

    emit("e2e_committed_ops_per_sec", round(ops, 1), "ops/s",
         detail=dict(
             requests=n, seconds=round(dt, 3),
             clients=args.clients, pipeline=args.pipeline,
             pipeline_depth=args.pipeline_depth,
             groups=(args.groups if sharded_e2e else 1),
             max_inflight_dispatches=int(
                 driver.cluster.max_inflight_dispatches),
             threaded_app=bool(args.threaded_app),
             p50_ms=(round(lat[nb // 2] * 1e3, 2) if nb else None),
             p95_ms=(round(lat[int(nb * .95)] * 1e3, 2)
                     if nb else None),
             p99_ms=(round(lat[int(nb * .99)] * 1e3, 2)
                     if nb else None),
             fence=bool(args.fence), audit=bool(args.audit),
             telemetry=bool(args.telemetry),
             phases=phase_sums(),
             trace=trace_detail,
             profile=profile_detail,
             health=health),
         obs=driver.obs, json_path=args.json)

    if args.ab_pipeline > 0 and args.pipeline_depth >= 2:
        # pipeline on/off A/B — the --audit overhead methodology:
        # ALTERNATING rounds, each variant scored by its fastest
        # (host-load noise on a shared core exceeds the effect), the
        # in-flight-depth counter proving the ON rounds actually
        # overlapped dispatches, per-variant phase attribution
        from benchmarks.reporting import ab_pipeline_rounds
        ab = ab_pipeline_rounds(
            driver, args.ab_pipeline, args.pipeline_depth,
            wave_ops)
        speedup = ab["on"] / max(ab["off"], 1e-9)
        print(f"pipeline A/B: {ab['off']:.0f} ops/s off vs "
              f"{ab['on']:.0f} ops/s on -> {speedup:.2f}x "
              f"(max in-flight dispatches {ab['depth_seen']})")
        emit("pipeline_speedup", round(speedup, 3), "x",
             detail=dict(off_ops_per_sec=round(ab["off"], 1),
                         on_ops_per_sec=round(ab["on"], 1),
                         rounds=args.ab_pipeline,
                         requests_per_round=n,
                         pipeline_depth=args.pipeline_depth,
                         max_inflight_dispatches=ab["depth_seen"],
                         groups=(args.groups if sharded_e2e else 1),
                         phases_on=ab["phases_on"],
                         phases_off=ab["phases_off"]),
             obs=driver.obs, json_path=args.json)

    if args.scan and args.ab_hostpath > 0:
        # host-path A/B — the one methodology every overhead/speedup
        # row shares (alternating best-of on the same shared core):
        # OFF = scalar per-entry host loops + per-field burst readback
        # + standalone replay fetch dispatches (the pre-PR data
        # plane); ON = vectorized window batch ops + the K-window
        # scan tier's consolidated readback. Phase sums attribute
        # exactly where the us went (host_encode / apply_replay_ack /
        # quorum_wait).
        from benchmarks.reporting import ab_variant_rounds
        from rdma_paxos_tpu.runtime import hostpath as hostpath_mod

        def apply_variant(on: bool):
            hostpath_mod.set_vectorized(on)
            driver.cluster.scan = on

        ab = ab_variant_rounds(driver, args.ab_hostpath,
                               apply_variant,
                               wave_ops)
        speedup = ab["on"] / max(ab["off"], 1e-9)

        def us_per_op(ops):
            return round(1e6 / ops, 2) if ops else None

        print(f"host-path A/B: {ab['off']:.0f} ops/s scalar vs "
              f"{ab['on']:.0f} ops/s vectorized+scan -> "
              f"{speedup:.2f}x ({us_per_op(ab['off'])} -> "
              f"{us_per_op(ab['on'])} us/op; "
              f"{driver.cluster.scan_dispatches} scan dispatches)")
        emit("host_path_speedup", round(speedup, 3), "x",
             detail=dict(off_ops_per_sec=round(ab["off"], 1),
                         on_ops_per_sec=round(ab["on"], 1),
                         off_us_per_op=us_per_op(ab["off"]),
                         on_us_per_op=us_per_op(ab["on"]),
                         rounds=args.ab_hostpath,
                         requests_per_round=n,
                         scan_k=max(driver.cluster.K_TIERS),
                         scan_dispatches=int(
                             driver.cluster.scan_dispatches),
                         groups=(args.groups if sharded_e2e else 1),
                         shared_core_caveat=(
                             "alternating best-of on shared CPU "
                             "cores; see REDIS_r06"),
                         phases_on=ab["phases_on"],
                         phases_off=ab["phases_off"]),
             obs=driver.obs, json_path=args.json)

    if args.audit:
        # e2e audit verdict (the whole workload ran digest-checked)
        # plus the A/B overhead row the acceptance criteria ask for
        summary = health.get("audit") or {}
        print(f"audit: {summary.get('indices_checked', 0)} index "
              f"checks over {summary.get('windows', 0)} windows, "
              f"{summary.get('findings', 0)} divergence finding(s)")
        ab = measure_audit_overhead()
        print(f"audit overhead: {ab['off']['ops_per_sec']} ops/s off "
              f"vs {ab['on']['ops_per_sec']} ops/s on "
              f"({ab['overhead_pct']}% — target <5%)")
        emit("audit_overhead_pct", ab["overhead_pct"], "%",
             detail=dict(off=ab["off"], on=ab["on"],
                         audit=ab["audit"], e2e_audit=summary),
             obs=driver.obs, json_path=args.json)

    if args.telemetry:
        # e2e proof the counters flowed (the driver's own device_*
        # series); the A/B overhead row runs AFTER driver.stop() —
        # the live driver keeps dispatching its own telemetry-on idle
        # steps from the poll loop, and that background host work
        # biases the on-variant rounds by 10+ points on a small box
        snap_counters = {
            k: v for k, v in metrics_snap["counters"].items()
            if k.startswith("device_")}
        print(f"device telemetry: {len(snap_counters)} series "
              f"exported during the workload")

    # replication check: every replica's app must converge to the same
    # key count (sharded: all G groups' committed streams replayed
    # into every replica's app)
    time.sleep(1.0)

    def kv_count(port):
        s = socket.create_connection(("127.0.0.1", port), timeout=10)
        f = s.makefile("rb")
        s.sendall(b"COUNT\n")
        out = f.readline().strip().decode()
        s.close()
        return out

    deadline = time.time() + 30
    while True:
        counts = [kv_count(p) for p in ports]
        if len(set(counts)) == 1 or time.time() > deadline:
            break
        time.sleep(0.5)
    print(f"replica kv counts: {counts} "
          + ("OK" if len(set(counts)) == 1 else "MISMATCH"))

    driver.stop()
    for a in apps:
        a.kill()
        a.wait()

    if args.scan and args.ab_hostpath > 0:
        # engine-closed-loop host-path A/B on the now-quiet process
        # (the --telemetry reasoning): isolates the data-plane delta
        # from client-thread GIL contention and app socket I/O — the
        # e2e row above measures the whole serving stack, this row
        # measures the driver host path itself
        hp = measure_host_path()
        print(f"host-path engine A/B: {hp['off']['ops_per_sec']} "
              f"ops/s scalar+burst vs {hp['on']['ops_per_sec']} "
              f"ops/s vectorized+scan -> {hp['speedup']}x "
              f"({hp['scan']['scan_dispatches']} scan dispatches)")
        emit("host_path_speedup_engine", hp["speedup"], "x",
             detail=dict(off=hp["off"], on=hp["on"], **hp["scan"],
                         shared_core_caveat=(
                             "engine closed loop, alternating "
                             "best-of on shared CPU cores")),
             obs=driver.obs, json_path=args.json)

    if args.repair:
        # on the now-quiet process (same reasoning as --telemetry):
        # the A/B measures the controller's findings scan, and the
        # MTTR round measures the whole self-healing loop in
        # step-domain time (deterministic, host-load independent)
        ab = measure_repair()
        mttr = ab["mttr"]
        print(f"repair overhead: {ab['off']['ops_per_sec']} ops/s off "
              f"vs {ab['on']['ops_per_sec']} ops/s on "
              f"({ab['overhead_pct']}% — target <5%)")
        print(f"MTTR: {mttr['mttr_steps']} steps corruption->re-admit "
              f"(detect {mttr['detection_steps']}, probation "
              f"{mttr['probation_steps']}), coverage_ok="
              f"{mttr['coverage_ok']}")
        emit("repair_overhead_pct", ab["overhead_pct"], "%",
             detail=dict(off=ab["off"], on=ab["on"]),
             obs=driver.obs, json_path=args.json)
        emit("mttr_steps", mttr["mttr_steps"], "steps",
             detail=mttr, obs=driver.obs, json_path=args.json)

    if args.read_ratio > 0:
        # on the now-quiet process (the --repair/--telemetry
        # reasoning): the A/B measures the read paths, not poll-loop
        # contention. The lease variant serves reads host-side from
        # the leaseholder; the log variant rides every read through
        # the replicated ring — what every linearizable read cost
        # before PR 10.
        rm = measure_read_mix(args.read_ratio)
        acc = rm["accounting"]
        print(f"read mix ({args.read_ratio:.0%} reads): "
              f"{rm['lease']['read_ops_per_sec']:.0f} reads/s leased "
              f"vs {rm['log']['read_ops_per_sec']:.0f} reads/s "
              f"through-log -> {rm['lease_read_speedup']}x "
              f"(lease-path accounting: "
              f"{acc['lease_variant']['lease']} reads)")
        emit("read_ops_per_sec", rm["lease"]["read_ops_per_sec"],
             "ops/s", detail=dict(read_ratio=args.read_ratio,
                                  variant="lease", **rm["lease"]),
             obs=driver.obs, json_path=args.json)
        emit("write_ops_per_sec", rm["lease"]["write_ops_per_sec"],
             "ops/s", detail=dict(read_ratio=args.read_ratio,
                                  variant="lease", **rm["lease"]),
             obs=driver.obs, json_path=args.json)
        emit("lease_read_speedup", rm["lease_read_speedup"], "x",
             detail=rm, obs=driver.obs, json_path=args.json)

    if args.watch_ratio > 0:
        # on the now-quiet process (the --read-ratio reasoning): the
        # A/B isolates the streams surface's cost on the write path —
        # the pump and CDC exporter run concurrently with the
        # committed workload, exactly as deployed
        wm = measure_watch_mix(args.watch_ratio)
        at = wm["attached"]
        print(f"watch mix ({args.watch_ratio:.0%} keyspace watched, "
              f"{wm['n_watchers']} watchers): "
              f"{at['watch_fanout_events_per_sec']:.0f} events/s "
              f"fan-out, cdc lag {wm['cdc']['lag']} "
              f"({wm['cdc']['exported']} exported), attach overhead "
              f"{wm['watch_attach_overhead_pct']}% (target <3%)")
        emit("watch_fanout_events_per_sec",
             at["watch_fanout_events_per_sec"], "events/s",
             detail=dict(watch_ratio=args.watch_ratio, **at),
             obs=driver.obs, json_path=args.json)
        emit("cdc_lag_entries", wm["cdc"]["lag"], "entries",
             detail=wm["cdc"], obs=driver.obs, json_path=args.json)
        emit("watch_attach_overhead_pct",
             wm["watch_attach_overhead_pct"], "%", detail=wm,
             obs=driver.obs, json_path=args.json)

    if args.txn:
        # on the now-quiet process (the --read-ratio reasoning): the
        # probes count dispatches on a dedicated txn=True geometry,
        # and the A/B isolates the fast path's cost on the write path
        tm = measure_txn()
        pr = tm["probe"]
        print(f"txn: cross-group 2PC commit = "
              f"{pr['twopc']['dispatches']} dispatches "
              f"(single-key put = {pr['single']['dispatches']}), "
              f"latency ratio {pr['latency_ratio']}x; mergeable "
              f"{tm['merge']['write_ops_per_sec']:.0f} ops/s vs "
              f"plain {tm['plain']['write_ops_per_sec']:.0f} ops/s "
              f"-> {tm['merge_throughput_ratio']}x (target >=0.9x)")
        emit("txn_commit_dispatches", pr["twopc"]["dispatches"],
             "dispatches", detail=pr, obs=driver.obs,
             json_path=args.json)
        emit("txn_commit_latency_ratio", pr["latency_ratio"], "x",
             detail=pr, obs=driver.obs, json_path=args.json)
        emit("txn_merge_throughput_ratio",
             tm["merge_throughput_ratio"], "x", detail=tm,
             obs=driver.obs, json_path=args.json)

    if args.serve_metrics is not None:
        # ops-plane overhead on the now-quiet process (the
        # --telemetry reasoning): series sampling + full rule set +
        # live scrapes on vs the bare cluster — target <2%
        ab = measure_export_overhead()
        print(f"export overhead: {ab['off']['ops_per_sec']} ops/s "
              f"off vs {ab['on']['ops_per_sec']} ops/s on "
              f"({ab['overhead_pct']}% — target <2%)")
        emit("export_overhead_pct", ab["overhead_pct"], "%",
             detail=dict(off=ab["off"], on=ab["on"],
                         export=ab["export"]),
             obs=driver.obs, json_path=args.json)

    if args.telemetry:
        # counters on vs off, alternating best-of (the PR 5 audit
        # methodology) — on the now-quiet process, so the row measures
        # the counter vector, not poll-loop contention
        ab = measure_telemetry_overhead()
        print(f"telemetry overhead: {ab['off']['ops_per_sec']} ops/s "
              f"off vs {ab['on']['ops_per_sec']} ops/s on "
              f"({ab['overhead_pct']}% — target <5%)")
        emit("telemetry_overhead_pct", ab["overhead_pct"], "%",
             detail=dict(off=ab["off"], on=ab["on"],
                         device_counters=ab["device_counters"],
                         e2e_series=len(snap_counters)),
             obs=driver.obs, json_path=args.json)

    if args.trace_overhead:
        # sampling on (production default + TraceContext) vs off, on
        # the now-quiet process — the tracing counterpart of the
        # export row above, same <2% end-to-end target
        ab = measure_trace_overhead()
        print(f"trace overhead: {ab['off']['ops_per_sec']} ops/s "
              f"off vs {ab['on']['ops_per_sec']} ops/s on "
              f"({ab['overhead_pct']}% — target <2%)")
        emit("trace_overhead_pct", ab["overhead_pct"], "%",
             detail=dict(off=ab["off"], on=ab["on"],
                         trace=ab["trace"]),
             obs=driver.obs, json_path=args.json)


if __name__ == "__main__":
    main()
