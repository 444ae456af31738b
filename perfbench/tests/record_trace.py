#!/usr/bin/env python3
"""Record the small profiler trace that ``test_trace_reduction.py`` reads.

Run on the machine with the chip (``chiprun -- python
perfbench/tests/record_trace.py``): a toy-geometry three-replica group
takes a few steps and bursts under ``jax.profiler`` with the harness's
own capture options and anchors; the ``.xplane.pb`` lands in
``chiprun_out/small_trace/`` and a dump of its structure is printed.
The recorded file is then gzipped and copied beside the test by hand,
with ``small.anchors.json``.
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    import jax

    import rdma_paxos_tpu as rp
    from perfbench.harness import trace as tr
    from rdma_paxos_tpu.runtime.sim import SimCluster

    out = os.path.join(ROOT, "chiprun_out", "small_trace")
    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(out)
    cfg = rp.LogConfig(n_slots=256, slot_bytes=64, window_slots=32,
                       batch_slots=16)
    mode = {"mode": "spmd"} if len(jax.devices()) >= 3 else {}
    c = SimCluster(cfg, 3, fanout="psum", **mode)
    c.step(timeouts=[0])
    for i in range(4):                      # compile outside the trace
        c.submit(0, b"SET warm %d" % i)
    c.step()
    c.submit(0, b"SET warm b")
    c.step_burst()
    cap = tr.Capture(os.path.join(out, "prof")).start()
    for i in range(3):
        c.submit(0, b"SET k%d v" % i)
        c.step()
        time.sleep(0.002)
    for i in range(2):
        for j in range(20):
            c.submit(0, b"SET b%d-%d v" % (i, j))
        c.step_burst()
    cap.stop()
    path = cap.xplane_path()
    dst = os.path.join(out, "small.xplane.pb")
    shutil.copy(path, dst)
    shutil.rmtree(os.path.join(out, "prof"), ignore_errors=True)
    with open(os.path.join(out, "small.anchors.json"), "w") as f:
        json.dump(cap.anchors, f)
    print("recorded", dst, os.path.getsize(dst), "bytes; anchors",
          cap.anchors)
    tr.dump_structure(dst)
    red = tr.reduce_trace(dst, cap.anchors)
    print({k: v for k, v in red.items() if k != "devices"})
    return 0


if __name__ == "__main__":
    sys.exit(main())
