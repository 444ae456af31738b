"""What a dispatch's host-to-device put costs: six arrays against one.

    python benchmarks/micro_put.py [--K 2] [--R 3] [--B 1024]
        [--slot-words 128] [--groups 0] [--mesh 0|1] [--iters 300]

``begin_burst`` handed the device six host arrays a dispatch (``data
[K,R,B,slot_words]``, ``meta [K,R,B,8]``, ``count [K,R]``, ``peer_mask
[R,R]``, ``applied [R]``, ``qdepth [R]``; under ``--groups G`` each
with the sharded engine's group axis before ``R``). This times that
put against ONE i32 array of the same words led by the mesh's axes
(``[R, rows, 128]`` / ``[G, R, rows, 128]``: ``consensus/step.py`` ``arg_layout``), at
a cell's shapes, on one chip (``jnp.asarray``) or on a replica mesh
(``jax.device_put`` with the programs' shardings). ISSUE 51's step 0;
kept so that a later session can read the premise again.

Prints ONE JSON line. ``call_us`` is the host's time in the put (what
``input_transfer_us`` reads), ``ready_us`` the time until the arrays
are on the device, ``call_after_put_us`` what a program's call costs
on arguments put just before it (the dispatch's case; what
``program_call_us`` reads) beside ``call_on_ready_us``. A number from a CPU run is no device metric: the
line names the device it ran on."""
import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from rdma_paxos_tpu.config import LogConfig
from rdma_paxos_tpu.consensus.step import META_W, arg_layout
from rdma_paxos_tpu.parallel.mesh import (
    axes_spec, build_mesh_2d, make_replica_mesh)


def timed(fn, iters):
    """Median host time of ``fn()`` and of ``fn()`` until its result
    is ready, microseconds, each over ``iters`` calls."""
    call, ready = [], []
    for _ in range(iters):
        t0 = time.perf_counter_ns()
        out = fn()
        t1 = time.perf_counter_ns()
        jax.block_until_ready(out)
        ready.append((time.perf_counter_ns() - t0) / 1e3)
        call.append((t1 - t0) / 1e3)
    return dict(call_us=statistics.median(call),
                ready_us=statistics.median(ready))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--K", type=int, default=2)
    ap.add_argument("--R", type=int, default=3)
    ap.add_argument("--B", type=int, default=1024)
    ap.add_argument("--slot-words", type=int, default=128)
    ap.add_argument("--groups", type=int, default=0)
    ap.add_argument("--mesh", type=int, default=0)
    ap.add_argument("--iters", type=int, default=300)
    a = ap.parse_args()
    K, R, B, sw, G = a.K, a.R, a.B, a.slot_words, a.groups
    cfg = LogConfig(n_slots=4 * B, slot_bytes=4 * sw, window_slots=B,
                    batch_slots=B)
    lead = (G, R) if G else (R,)
    rng = np.random.default_rng(0)
    six = (rng.integers(0, 1 << 30, (K, *lead, B, sw), np.int32),
           rng.integers(0, 1 << 30, (K, *lead, B, META_W), np.int32),
           np.full((K, *lead), B, np.int32),
           np.ones((*lead, R), np.int32),
           np.zeros(lead, np.int32), np.zeros(lead, np.int32))
    lay = arg_layout(cfg, R, K)
    one = rng.integers(0, 1 << 30, lay.shape(lead), np.int32)

    if a.mesh:
        mesh = (build_mesh_2d(1, R) if G else make_replica_mesh(R))
        rows = jax.sharding.NamedSharding(mesh, axes_spec(mesh))
        stacks = jax.sharding.NamedSharding(mesh, axes_spec(mesh, 1))
        shard6 = (stacks,) * 3 + (rows,) * 3

        def put6():
            return jax.device_put(six, shard6)

        def put1():
            return jax.device_put(one, rows)
    else:
        def put6():
            return tuple(jnp.asarray(x) for x in six)

        def put1():
            return jnp.asarray(one)

    for fn in (put6, put1):          # allocator and transfer paths warm
        for _ in range(20):
            jax.block_until_ready(fn())
    dev = put1()
    res = dict(
        device=dict(platform=jax.devices()[0].platform,
                    kind=jax.devices()[0].device_kind,
                    count=jax.device_count()),
        shapes=dict(K=K, R=R, B=B, slot_words=sw, groups=G,
                    mesh=bool(a.mesh)),
        six_bytes=sum(x.nbytes for x in six), one_bytes=one.nbytes,
        six=timed(put6, a.iters), one=timed(put1, a.iters))
    # what a program's CALL costs on arguments whose transfer has only
    # just been started (the dispatch's case) against ready ones
    first = jax.jit(lambda *xs: xs[0].reshape(-1)[:1] + 1)
    for args in ((dev,), put6()):
        jax.block_until_ready(first(*args))
    res["noop_program_us"] = timed(lambda: first(dev), a.iters)["ready_us"]
    res["call_on_ready_us"] = timed(lambda: first(dev), a.iters)["call_us"]

    def call_after(put):
        calls = []
        for _ in range(a.iters):
            args = put()
            t0 = time.perf_counter_ns()
            out = first(*(args if isinstance(args, tuple) else (args,)))
            calls.append((time.perf_counter_ns() - t0) / 1e3)
            jax.block_until_ready(out)
        return statistics.median(calls)
    res["call_after_put_us"] = dict(six=call_after(put6),
                                    one=call_after(put1))
    res["call_us_saved"] = res["six"]["call_us"] - res["one"]["call_us"]
    print(json.dumps(res))


if __name__ == "__main__":
    main()
