#!/usr/bin/env python
"""LogGP-style fabric measurement — the ``SRV_TYPE_LOGGP`` mode analog.

The reference measures o (send overhead), o_poll, L (latency), G (per-byte
gap) of the RDMA fabric with median-of-1000 sampling
(``rc_get_loggp_params``, ``dare_ibv_rc.c:3323-3597``). Here the unit of
communication is the replica step, so the measured quantities are:

  o+L  — fixed per-step overhead: step time with an empty window
         (heartbeat-only step) — control gather + claim gather + empty
         fan-out
  G    — per-byte gap: slope of step time vs window payload bytes
  g    — per-entry gap: slope vs entries per step at fixed bytes

measured separately for the psum fan-out (production O(W) broadcast) and
the gather fan-out (partition-capable O(R*W)).

Timing rules: each (config, fill, fanout) sample runs in its OWN
subprocess, timing K-step scans whose timed region ends with a value read
of the final commit (dispatch is asynchronous); the parent never touches
the device, because a chip belongs to one process at a time.

    python benchmarks/loggp.py [--json out.json]
    RP_BENCH_CPU=1 python benchmarks/loggp.py
"""

import argparse
import json
import os
import subprocess
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

R = 3
K = 64
REPS = 4
BASE = dict(n_slots=8192, window_slots=256, batch_slots=256)


def measure_row(slot_bytes: int, fill: int, fanout: str) -> float:
    """One subprocess: per-step µs for this configuration."""
    import time

    import jax
    if os.environ.get("RP_BENCH_CPU", "0") == "1":
        jax.config.update("jax_platforms", "cpu")
    import functools

    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from rdma_paxos_tpu.config import LogConfig
    from rdma_paxos_tpu.consensus.log import (
        EntryType, M_LEN, M_TYPE, META_W)
    from rdma_paxos_tpu.consensus.step import StepInput, replica_step
    from rdma_paxos_tpu.parallel.mesh import REPLICA_AXIS, stack_states

    cfg = LogConfig(slot_bytes=slot_bytes, **BASE)
    use_pallas = jax.default_backend() == "tpu"
    core = functools.partial(replica_step, cfg=cfg, n_replicas=R,
                             axis_name=REPLICA_AXIS,
                             use_pallas=use_pallas, fanout=fanout,
                             elections=False)
    fullc = functools.partial(replica_step, cfg=cfg, n_replicas=R,
                              axis_name=REPLICA_AXIS,
                              use_pallas=use_pallas, fanout=fanout,
                              elections=True)
    vstep = jax.vmap(core, in_axes=(0, 0), axis_name=REPLICA_AXIS)
    vfull = jax.vmap(fullc, in_axes=(0, 0), axis_name=REPLICA_AXIS)
    B = cfg.batch_slots
    bd = jnp.zeros((R, B, cfg.slot_words), jnp.int32)
    bm = (jnp.zeros((R, B, META_W), jnp.int32)
          .at[:, :, M_TYPE].set(int(EntryType.SEND))
          .at[:, :, M_LEN].set(cfg.slot_bytes))
    peer = jnp.ones((R, R), jnp.int32)

    def make_inp(st, count, bd, bm, peer):
        return StepInput(
            batch_data=bd, batch_meta=bm,
            batch_count=jnp.full((R,), count, jnp.int32),
            timeout_fired=jnp.zeros((R,), jnp.int32),
            peer_mask=peer, apply_done=st.commit,
            queue_depth=jnp.zeros((R,), jnp.int32))

    @jax.jit
    def elect(st, bd, bm, peer):
        import dataclasses
        inp = dataclasses.replace(
            make_inp(st, 0, bd, bm, peer),
            timeout_fired=jnp.zeros((R,), jnp.int32).at[0].set(1))
        s2, _ = vfull(st, inp)
        return s2

    @jax.jit
    def scan_k(st, bd, bm, peer):
        def body(s, _):
            s, out = vstep(s, make_inp(s, fill, bd, bm, peer))
            return s, out.commit[0]
        return lax.scan(body, st, None, length=K)

    st = stack_states(cfg, R, R)
    st = elect(st, bd, bm, peer)
    scan_c = scan_k.lower(st, bd, bm, peer).compile()
    t0 = time.perf_counter()
    for _ in range(REPS):
        st, cs = scan_c(st, bd, bm, peer)
    _ = int(np.asarray(st.commit[0]))     # timed: forces the drain
    dt = time.perf_counter() - t0
    return dt / (REPS * K) * 1e6


def run_row(slot_bytes: int, fill: int, fanout: str,
            samples: int = 3) -> float:
    """Median of ``samples`` independent subprocesses."""
    vals = []
    for _ in range(samples):
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--row",
             json.dumps([slot_bytes, fill, fanout])],
            capture_output=True, text=True)
        val = None
        for ln in proc.stdout.splitlines():
            if ln.startswith("ROWJSON:"):
                val = json.loads(ln[len("ROWJSON:"):])
                break
        if val is None:
            raise RuntimeError("row %s failed: %s"
                               % ((slot_bytes, fill, fanout),
                                  proc.stderr[-2000:]))
        vals.append(val)
    return sorted(vals)[len(vals) // 2]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default=None)
    ap.add_argument("--row", default=None)
    args = ap.parse_args()
    if args.row is not None:
        sb, fill, fanout = json.loads(args.row)
        print("ROWJSON:" + json.dumps(measure_row(sb, fill, fanout)))
        return

    out = {"metric": "loggp_step_parameters",
           "samples_per_row": REPS * K,
           "rows": {}}
    for fanout in ("psum", "gather"):
        o_plus_l = run_row(256, 0, fanout)       # empty window
        t_small = run_row(128, 256, fanout)      # G: bytes slope
        t_big = run_row(1024, 256, fanout)
        dbytes = 256 * (1024 - 128)
        g_ns_byte = (t_big - t_small) * 1e3 / dbytes
        t_few = run_row(256, 32, fanout)         # g: entries slope
        t_many = run_row(256, 256, fanout)
        g_ns_entry = (t_many - t_few) * 1e3 / (256 - 32)
        out["rows"][fanout] = dict(
            o_plus_L_us=round(o_plus_l, 1),
            G_ns_per_byte=round(g_ns_byte, 3),
            g_ns_per_entry=round(g_ns_entry, 1),
            full_step_us=round(t_many, 1),
        )
    # backend from a child (the parent must not touch the device)
    probe = subprocess.run(
        [sys.executable, "-c",
         "import jax,os\n"
         "import sys\n"
         "sys.path.insert(0, %r)\n"
         "if os.environ.get('RP_BENCH_CPU','0')=='1':\n"
         "    jax.config.update('jax_platforms','cpu')\n"
         "print(jax.default_backend())" % os.path.dirname(
             os.path.dirname(os.path.abspath(__file__)))],
        capture_output=True, text=True)
    out["backend"] = probe.stdout.strip().splitlines()[-1] \
        if probe.stdout.strip() else "unknown"
    print(json.dumps(out, indent=2))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(out, f, indent=2)
    from benchmarks.reporting import emit
    emit("loggp_step_parameters",
         out["rows"]["psum"]["o_plus_L_us"], "us",
         detail=dict(backend=out["backend"], rows=out["rows"],
                     samples_per_row=out["samples_per_row"]))


if __name__ == "__main__":
    main()
