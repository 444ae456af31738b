#!/usr/bin/env python
"""Per-replica step cost vs group size R, under shard_map at BENCH
geometry — the flat-in-R evidence for ANALYSIS_R_SCALING.md.

Every topology available in this environment executes all R replicas'
device work on one execution unit (virtual CPU devices share one core),
so total step time grows ~linearly with R; what the design controls —
and what a real R-chip mesh runs per chip — is step time DIVIDED BY R.
This driver measures exactly that, with the honest protocol (timed
region ends with a value read), at the same geometry bench.py runs
(n_slots=8192, slot_bytes=128, window=batch=2048), psum fan-out.

    python benchmarks/r_scaling.py [--json out.json]
"""

import argparse
import json
import os
import subprocess
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def run_row(R: int, iters: int) -> dict:
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__),
         "--row", str(R), "--iters", str(iters)],
        capture_output=True, text=True)
    for ln in proc.stdout.splitlines():
        if ln.startswith("ROWJSON:"):
            return json.loads(ln[len("ROWJSON:"):])
    raise RuntimeError("R=%d failed: %s" % (R, proc.stderr[-2000:]))


def measure(R: int, iters: int) -> dict:
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=8")
    import jax
    jax.config.update("jax_platforms", "cpu")
    import time

    import jax.numpy as jnp
    import numpy as np

    from rdma_paxos_tpu.config import LogConfig
    from rdma_paxos_tpu.consensus.log import EntryType, M_LEN, M_TYPE
    from rdma_paxos_tpu.consensus.step import arg_layout
    from rdma_paxos_tpu.parallel.mesh import (
        build_spmd_burst, build_spmd_step, make_replica_mesh,
        stack_states)

    cfg = LogConfig(n_slots=8192, slot_bytes=128, window_slots=2048,
                    batch_slots=2048)
    mesh = make_replica_mesh(R)
    shard = jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec("replica"))
    B, K = cfg.batch_slots, 8
    # a dispatch's host inputs are ONE packed array, a row a replica
    lay = arg_layout(cfg, R, K)
    packed_np = lay.idle((R,))
    parts = lay.views(packed_np)
    parts["meta"][..., M_TYPE] = int(EntryType.SEND)
    parts["meta"][..., M_LEN] = 16
    parts["count"][:] = B
    packed = jax.device_put(packed_np, shard)
    applied_at = divmod({n: o for n, o, _ in lay.fields}["applied"], 128)

    step = build_spmd_step(cfg, R, mesh, fanout="psum", donate=False)
    burst = build_spmd_burst(cfg, R, mesh, fanout="psum")
    state = jax.device_put(stack_states(cfg, R, R), shard)
    lay1 = arg_layout(cfg, R)
    inp_np = lay1.idle((R,))
    lay1.split(inp_np)["timeout"][0] = 1
    state, _ = step(state, jax.device_put(inp_np, shard))   # election

    state, outs = burst(state, packed)     # warmup compile + run
    jax.block_until_ready(outs.commit)
    pre = int(np.asarray(state.commit)[0])
    t0 = time.perf_counter()
    for _ in range(iters):
        # echo applies => pruning (a copy of the cursors: burst donates
        # the state; the same buffer cannot also be an arg)
        packed = packed.at[(slice(None),) + applied_at].set(state.commit)
        state, outs = burst(state, packed)
    final = int(np.asarray(state.commit)[0])   # forces drain (uniform
    dt = time.perf_counter() - t0              # protocol w/ bench.py)
    steps = iters * K
    return dict(R=R, step_us=dt / steps * 1e6,
                per_replica_us=dt / steps / R * 1e6,
                committed=final - pre,
                ops=float((final - pre) / dt))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default=None)
    ap.add_argument("--row", type=int, default=None)
    ap.add_argument("--iters", type=int, default=6)
    args = ap.parse_args()
    if args.row is not None:
        print("ROWJSON:" + json.dumps(measure(args.row, args.iters)))
        return
    rows = [run_row(R, args.iters) for R in (3, 5, 7)]
    out = dict(metric="per_replica_step_cost_vs_R",
               topology="shard_map over virtual CPU devices "
                        "(one core!), bench geometry, psum fan-out",
               rows=rows)
    print(json.dumps(out, indent=2))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(out, f, indent=2)
    from benchmarks.reporting import emit
    emit("per_replica_step_cost_vs_R", rows[0]["per_replica_us"], "us",
         detail=dict(topology=out["topology"], rows=rows))


if __name__ == "__main__":
    main()
