#!/usr/bin/env python
"""Per-host node launcher — the per-machine half of ``benchmarks/run.sh``.

Run one of these on every host of the group (here: every process), with
the same coordinator address; each starts its replica daemon, optionally
its unmodified app under the interposition shim, and loops.

    server_idx=0 group_size=3 python benchmarks/launch_node.py \
        --coordinator host0:9900 --workdir /tmp/rp --app-port 7700 \
        --iterations 2000
"""

import argparse
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

NATIVE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "native")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--coordinator", required=True)
    ap.add_argument("--workdir", required=True)
    ap.add_argument("--app-port", type=int, default=0)
    ap.add_argument("--app-cmd", default="")
    ap.add_argument("--iterations", type=int, default=5000)
    ap.add_argument("--period", type=float, default=0.0)
    ap.add_argument("--config", default="")
    args = ap.parse_args()

    idx = int(os.environ["server_idx"])
    n = int(os.environ["group_size"])

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    if os.environ.get("RP_BENCH_CPU", "1") == "1":
        jax.config.update("jax_platforms", "cpu")
    # persistent compile cache: the step/burst programs are identical
    # across node restarts — never pay a mid-serving JIT pause twice
    from rdma_paxos_tpu.utils.compile_cache import use_compile_cache
    use_compile_cache()

    from rdma_paxos_tpu.config import LogConfig, TimeoutConfig, load_config
    from rdma_paxos_tpu.runtime.node import NodeDaemon

    if args.config:
        cfg, timing, _ = load_config(args.config)
    else:
        cfg = LogConfig(n_slots=1024, slot_bytes=256, window_slots=64,
                        batch_slots=64)
        timing = TimeoutConfig(elec_timeout_low=0.5, elec_timeout_high=1.0)

    node = NodeDaemon(cfg, process_id=idx, num_processes=n,
                      coordinator=args.coordinator, workdir=args.workdir,
                      app_port=args.app_port or None, timeout_cfg=timing)
    node.prewarm_burst()     # collective: compile bursts out of serving

    app = None
    if args.app_port:
        cmd = (args.app_cmd.split() if args.app_cmd
               else [os.path.join(NATIVE, "toyserver"),
                     str(args.app_port)])
        env = dict(os.environ)
        env["LD_PRELOAD"] = os.path.join(NATIVE, "interpose.so")
        env["RP_PROXY_SOCK"] = node.sock_path
        app = subprocess.Popen(cmd, env=env, stderr=subprocess.DEVNULL)
        time.sleep(0.2)

    try:
        node.run_iterations(args.iterations, period=args.period)
    finally:
        node.close()
        if app is not None:
            app.kill()
            app.wait()


if __name__ == "__main__":
    main()
