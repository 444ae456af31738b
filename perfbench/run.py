#!/usr/bin/env python3
"""perfbench — the served-path benchmark of rdma_paxos_tpu.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process owns the chip(s): it builds the cell's deployment, warms it
with the cell's own traffic, measures ``--seconds``, checks the answers
outside the window, prints lines for humans and, LAST, the one JSON
object the driver reads. With ``--trace 0`` its ``metrics`` are the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics,
read in a shorter, profiled window. It exits non-zero and prints no
result line unless JAX reports a TPU whose ``device_kind`` is in
``perfbench/peaks.json`` with as many chips as the cell asks for.

``--rehearse-cpu`` (toy geometry, 4 virtual CPU devices) exists for the
sandbox and for ``perfbench/tests``; its line says ``"platform":
"cpu"`` and is never a result. ``--fault <name>`` breaks the deployment
underneath the run, for the runs that show ``correct`` can come out
false. See ``perfbench/README.md`` for how cells are added.
"""

import time

T_START = time.monotonic()      # set-up is counted from here

import argparse                 # noqa: E402
import os                       # noqa: E402
import sys                      # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--fault", default=None)
    args = ap.parse_args()
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=4")
    sys.path.insert(0, ROOT)
    from perfbench.harness import core
    return core.run(args, T_START)


if __name__ == "__main__":
    sys.exit(main())
