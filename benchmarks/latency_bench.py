#!/usr/bin/env python
"""Commit-latency benchmark — the p99<50µs frontier (BASELINE.md).

The reference commits in single-digit µs via a busy RDMA commit loop
(``rc_write_remote_logs(wait_for_commit=1)``, ``dare_ibv_rc.c:1870-1948``);
BASELINE.md sets the TPU target at p99 commit < 50 µs. This bench measures
the regimes that bound the TPU design:

* **bare mode** — a trivial jitted program's dispatch percentiles: the
  environment's irreducible host→device round-trip floor, the yardstick
  the step dispatch is judged against.
* **dispatch mode** — one host→device dispatch per protocol step at small
  batch (1..64): the client-visible commit latency of a step-per-poll
  driver. Reports p50/p95/p99 over individual dispatches.
* **pipelined mode** — D step dispatches kept in flight (async dispatch;
  block only on the oldest): per-step completion interval of an
  overlapped driver — the dispatch-overlap analog of the reference's
  busy commit loop always having work posted on the NIC.
* **scan mode** — K steps fused into one dispatch (``lax.scan``): the
  amortized per-step device latency — the floor a multi-step burst
  driver approaches.

HARNESS RULE: every input array is PASSED AS AN ARGUMENT to the jitted
step — a closure-captured jnp/np array is embedded in the lowered module
as a literal, so the program under test would carry (and compile, and
cache) its own inputs instead of reading them from device memory the way
the served step does.

Config is latency-tuned (small ring/window — ring gather cost scales with
rows), 3 replicas, psum fan-out, Pallas quorum scan on TPU.

    python benchmarks/latency_bench.py [--json out.json]
"""

import argparse
import collections
import dataclasses
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from rdma_paxos_tpu.config import LogConfig
from rdma_paxos_tpu.consensus.log import EntryType, M_LEN, M_TYPE, META_W
from rdma_paxos_tpu.consensus.step import StepInput, replica_step
from rdma_paxos_tpu.parallel.mesh import REPLICA_AXIS, stack_states

R = 3
K_SCAN = 256


def _pcts(lat):
    lat = sorted(lat)
    n = len(lat)
    return dict(p50_us=float(lat[n // 2] * 1e6),
                p95_us=float(lat[int(n * .95)] * 1e6),
                p99_us=float(lat[min(int(n * .99), n - 1)] * 1e6))


def measure_bare(iters: int = 400):
    """Dispatch percentiles of a trivial program — the environment floor."""
    @jax.jit
    def triv(x):
        return x + 1
    x = jnp.zeros((8,), jnp.int32)
    x = triv(x)
    x.block_until_ready()
    lat = []
    for _ in range(iters):
        t0 = time.perf_counter()
        y = triv(x)
        y.block_until_ready()
        lat.append(time.perf_counter() - t0)
    return _pcts(lat)


def build(cfg: LogConfig, batch: int, use_pallas=None):
    if use_pallas is None:
        # the Pallas quorum kernel pays a fixed launch cost that only
        # amortizes at throughput geometry; the latency profile uses the
        # jnp scan
        use_pallas = (jax.default_backend() == "tpu"
                      and cfg.batch_slots >= 64)
    # the hot path dispatches the STABLE step (elections statically
    # removed — exactly what the production driver runs between timer
    # events); elections use the full step
    core = functools.partial(replica_step, cfg=cfg, n_replicas=R,
                             axis_name=REPLICA_AXIS, use_pallas=use_pallas,
                             fanout="psum", elections=False)
    full = functools.partial(replica_step, cfg=cfg, n_replicas=R,
                             axis_name=REPLICA_AXIS, use_pallas=use_pallas,
                             fanout="psum", elections=True)
    vstep = jax.vmap(core, in_axes=(0, 0), axis_name=REPLICA_AXIS)
    vfull = jax.vmap(full, in_axes=(0, 0), axis_name=REPLICA_AXIS)

    # input arrays built EAGERLY and passed as arguments (see module
    # docstring: captured constants poison dispatch on this backend)
    data = jnp.zeros((R, cfg.batch_slots, cfg.slot_words), jnp.int32)
    meta = jnp.zeros((R, cfg.batch_slots, META_W), jnp.int32)
    meta = meta.at[:, :, M_TYPE].set(int(EntryType.SEND))
    meta = meta.at[:, :, M_LEN].set(16)
    peer = jnp.ones((R, R), jnp.int32)
    consts = (data, meta, peer)

    def make_inp(state, count, data, meta, peer):
        return StepInput(
            batch_data=data, batch_meta=meta,
            batch_count=jnp.full((R,), count, jnp.int32),
            timeout_fired=jnp.zeros((R,), jnp.int32),
            peer_mask=peer, apply_done=state.commit,
            queue_depth=jnp.zeros((R,), jnp.int32))

    @jax.jit
    def one(state, data, meta, peer):
        st, out = vstep(state, make_inp(state, batch, data, meta, peer))
        return st, out.commit[0]

    @jax.jit
    def scan_k(state, data, meta, peer):
        def body(st, _):
            st, out = vstep(st, make_inp(st, batch, data, meta, peer))
            return st, out.commit[0]
        return jax.lax.scan(body, state, None, length=K_SCAN)

    @jax.jit
    def elect(state, data, meta, peer):
        inp = dataclasses.replace(
            make_inp(state, 0, data, meta, peer),
            timeout_fired=jnp.zeros((R,), jnp.int32).at[0].set(1))
        st, _ = vfull(state, inp)
        return st

    return elect, one, scan_k, consts


def measure(cfg: LogConfig, batch: int, iters: int = 400,
            use_pallas=None, pipeline_depth: int = 4):
    # every timed sample also lands in an obs registry histogram so the
    # row JSON carries full bucketed distributions (not just the three
    # percentiles) for future BENCH_* rounds
    from rdma_paxos_tpu.obs.metrics import (
        LATENCY_BUCKETS_US as US_BUCKETS, MetricsRegistry)
    reg = MetricsRegistry()
    elect, one, scan_k, consts = build(cfg, batch, use_pallas)
    state = stack_states(cfg, R, R)
    state = elect(state, *consts)
    # warmup / compile
    state, c = one(state, *consts)
    jax.block_until_ready(c)
    lat = []
    for _ in range(iters):
        t0 = time.perf_counter()
        state, c = one(state, *consts)
        c.block_until_ready()
        lat.append(time.perf_counter() - t0)
        reg.observe("dispatch_latency_us", lat[-1] * 1e6,
                    buckets=US_BUCKETS, batch=batch)
    disp = _pcts(lat)

    # pipelined mode: keep D dispatches in flight; each iteration blocks
    # only on the oldest commit result. The completion interval is the
    # sustained per-step latency of an overlapped driver.
    q = collections.deque()
    for _ in range(pipeline_depth):
        state, c = one(state, *consts)
        q.append(c)
    intervals = []
    t_prev = time.perf_counter()
    for _ in range(iters):
        state, c = one(state, *consts)
        q.append(c)
        q.popleft().block_until_ready()
        t_now = time.perf_counter()
        intervals.append(t_now - t_prev)
        reg.observe("pipelined_interval_us", (t_now - t_prev) * 1e6,
                    buckets=US_BUCKETS, batch=batch)
        t_prev = t_now
    while q:
        q.popleft().block_until_ready()
    pipe = _pcts(intervals)

    # scan mode: amortized per-step device latency. Dispatch is
    # asynchronous, so the one aggregate timed region ENDS WITH the
    # commit read, which cannot return before every step has run.
    state2 = stack_states(cfg, R, R)
    state2 = elect(state2, *consts)
    # compile outside the timed region
    scan_c = scan_k.lower(state2, *consts).compile()
    state_pre = jax.block_until_ready(state2)
    reps = 8
    t0 = time.perf_counter()
    for _ in range(reps):
        state2, cs = scan_c(state2, *consts)
    final = int(np.asarray(state2.commit[0]))     # timed: forces drain
    scan_dt = time.perf_counter() - t0
    per_step_us = scan_dt / (reps * K_SCAN) * 1e6
    committed = final - int(np.asarray(state_pre.commit[0]))

    # host-visible number: one step PLUS reading its commit back (the
    # mode a per-step-readback driver lives in)
    rb = []
    st3, c3 = one(state2, *consts)
    for _ in range(20):
        t0 = time.perf_counter()
        st3, c3 = one(st3, *consts)
        _ = int(np.asarray(c3))
        rb.append(time.perf_counter() - t0)
    rb.sort()

    return dict(batch=batch, dispatch=disp,
                pipelined=dict(depth=pipeline_depth, **pipe),
                scan_step_us=float(per_step_us),
                commit_throughput_scan=float(committed / scan_dt),
                step_plus_readback_ms_p50=float(rb[len(rb) // 2] * 1e3),
                metrics=reg.snapshot())


# the three measured profiles: latency geometry at batch 1 and 8, and
# the throughput geometry the redis bench drives
ROWS = {
    "1": (dict(n_slots=256, slot_bytes=64, window_slots=16,
               batch_slots=8), 1),
    "8": (dict(n_slots=256, slot_bytes=64, window_slots=16,
               batch_slots=8), 8),
    "64": (dict(n_slots=256, slot_bytes=64, window_slots=64,
                batch_slots=64), 64),
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default=None)
    ap.add_argument("--iters", type=int, default=400)
    # internal: run ONE row and print its JSON (each row runs in a
    # fresh process, so no row inherits another's executables or
    # allocator state)
    ap.add_argument("--row", default=None,
                    choices=list(ROWS) + ["bare"])
    args = ap.parse_args()

    if args.row is not None:
        if args.row == "bare":
            row = measure_bare(args.iters)
        else:
            cfg_kw, batch = ROWS[args.row]
            row = measure(LogConfig(**cfg_kw), batch, args.iters)
            row["config"] = cfg_kw
        row["backend"] = jax.default_backend()
        print("ROWJSON:" + json.dumps(row))
        return

    # the parent NEVER touches the device: a chip belongs to one
    # process at a time, and a parent holding it would make every row
    # subprocess fail or hang
    import subprocess

    def run_row(key):
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__),
             "--row", key, "--iters", str(args.iters)],
            capture_output=True, text=True)
        for ln in proc.stdout.splitlines():
            if ln.startswith("ROWJSON:"):
                return json.loads(ln[len("ROWJSON:"):])
        raise RuntimeError("row %s failed: %s" % (key,
                                                  proc.stderr[-2000:]))

    bare = run_row("bare")
    backend = bare.pop("backend")
    rows = [run_row(key) for key in ROWS]
    for r in rows:
        r.pop("backend", None)
    out = dict(
        metric="commit_latency_frontier",
        backend=backend,
        replicas=R,
        target_p99_us=50.0,
        methodology=(
            "'dispatch'/'pipelined' rows time enqueue to "
            "block_until_ready; 'scan_step_us' is amortized device time "
            "(timed region ends with a value read of the final commit); "
            "'step_plus_readback_ms_p50' is the host-visible per-step "
            "cost when reading every step. Each row runs in a fresh "
            "process."),
        bare_dispatch=bare,
        batch1_vs_bare_p99=round(rows[0]["dispatch"]["p99_us"]
                                 / bare["p99_us"], 2),
        rows=rows,
    )
    print(json.dumps(out, indent=2))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(out, f, indent=2)
    # the standardized BENCH line (benchmarks.reporting): headline =
    # batch-1 dispatch p99 vs the 50 µs target; bulky per-row registry
    # snapshots stay in the artifact doc only
    from benchmarks.reporting import emit
    emit("commit_latency_frontier",
         rows[0]["dispatch"]["p99_us"], "us",
         detail=dict(
             backend=backend, target_p99_us=50.0,
             bare_p99_us=bare["p99_us"],
             batch1_vs_bare_p99=out["batch1_vs_bare_p99"],
             rows=[{k: v for k, v in r.items() if k != "metrics"}
                   for r in rows]))


if __name__ == "__main__":
    main()
