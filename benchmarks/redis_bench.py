#!/usr/bin/env python
"""The reference's EXACT headline benchmark: ``redis-benchmark -t set``
against the leader of a replicated group of pristine Redis servers under
``LD_PRELOAD=interpose.so`` (``benchmarks/run.sh:73-82``).

Builds Redis 2.8.17 from the reference tree's vendored upstream tarball
(the version ``apps/redis/mk`` targets), boots N replicas + the consensus
driver, elects, runs redis-benchmark with the reference's flags, and
checks follower state equality (DBSIZE).

    python benchmarks/redis_bench.py --replicas 3 -n 10000 -c 8 -P 64
"""

import argparse
import os
import socket
import subprocess
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

NATIVE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "native")
TARBALL = "/root/reference/apps/redis/redis-2.8.17.tar.gz"
BUILD_ROOT = "/tmp/rp_redis_build"
SRC = os.path.join(BUILD_ROOT, "redis-2.8.17", "src")


def ensure_redis() -> str:
    """Build pristine Redis once from the reference tree's vendored
    upstream tarball; returns the redis-server path. Raises
    FileNotFoundError (no tarball) or RuntimeError (build failure) —
    the single build recipe shared by the bench and the e2e tests."""
    server = os.path.join(SRC, "redis-server")
    if os.path.exists(server):
        return server
    if not os.path.exists(TARBALL):
        raise FileNotFoundError("reference redis tarball unavailable")
    os.makedirs(BUILD_ROOT, exist_ok=True)
    subprocess.run(["tar", "xzf", TARBALL], cwd=BUILD_ROOT, check=True)
    r = subprocess.run(["make", "MALLOC=libc", "-j1"],
                       cwd=os.path.join(BUILD_ROOT, "redis-2.8.17"),
                       capture_output=True, timeout=900)
    if r.returncode != 0 or not os.path.exists(server):
        raise RuntimeError("redis build failed: %s"
                           % r.stderr.decode()[-300:])
    return server


def resp(port, line):
    s = socket.create_connection(("127.0.0.1", port), timeout=10)
    f = s.makefile("rb")
    s.sendall(line + b"\r\n")
    out = f.readline().strip()
    s.close()
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--replicas", type=int, default=3)
    ap.add_argument("-n", type=int, default=10000)
    ap.add_argument("-c", type=int, default=8)
    ap.add_argument("-P", type=int, default=64,
                    help="redis-benchmark pipeline depth")
    ap.add_argument("-r", type=int, default=0,
                    help="randomize keys over this keyspace (stronger "
                         "follower-equality evidence than the default "
                         "single-key workload)")
    ap.add_argument("--port-base", type=int, default=9860)
    ap.add_argument("--profile", action="store_true",
                    help="wall-time phase accounting of the driver poll "
                         "loop (device step / replay / apply / sync sums)")
    ap.add_argument("--n-slots", type=int, default=8192)
    ap.add_argument("--slot-bytes", type=int, default=256)
    ap.add_argument("--window-slots", type=int, default=1024)
    ap.add_argument("--batch-slots", type=int, default=1024)
    ap.add_argument("--fanout", default="psum",
                    choices=("psum", "gather"),
                    help="window fan-out: psum is the production "
                         "full-connectivity config (O(W) per replica)")
    ap.add_argument("--sync-period", type=float, default=0.2,
                    help="store fdatasync cadence (durability matches "
                         "the reference's quorum-memory contract)")
    ap.add_argument("--pipeline-depth", type=int, default=2,
                    help="driver dispatch-pipeline depth (0/1 = fully "
                         "serial loop)")
    ap.add_argument("--ab-pipeline", type=int, default=2,
                    help="rounds per variant for the pipeline on/off "
                         "A/B (alternating best-of); emits a "
                         "pipeline_speedup row. 0 disables")
    ap.add_argument("--scan", type=int, default=0, metavar="K",
                    help="device-resident K-window scan tier (see "
                         "run_bench --scan): one consolidated "
                         "readback per up-to-K fused steps")
    ap.add_argument("--ab-hostpath", type=int, default=2,
                    help="with --scan: rounds per variant for the "
                         "host-path A/B (vectorized+scan vs scalar "
                         "reference+no-scan, alternating best-of); "
                         "emits host_path_speedup. 0 disables")
    args = ap.parse_args()

    try:
        ensure_redis()
    except (FileNotFoundError, RuntimeError) as e:
        raise SystemExit(str(e))
    import jax
    if os.environ.get("RP_BENCH_CPU", "1") == "1":
        jax.config.update("jax_platforms", "cpu")
    # persistent compile cache: burst-tier compiles are seconds each and
    # identical across runs — never pay them twice on one machine
    from rdma_paxos_tpu.utils.compile_cache import use_compile_cache
    use_compile_cache()
    from rdma_paxos_tpu.config import LogConfig, TimeoutConfig
    from rdma_paxos_tpu.runtime.driver import ClusterDriver

    cfg = LogConfig(n_slots=args.n_slots, slot_bytes=args.slot_bytes,
                    window_slots=args.window_slots,
                    batch_slots=args.batch_slots)
    ports = [args.port_base + i for i in range(args.replicas)]
    wd = tempfile.mkdtemp(prefix="rp_redisbench_")
    subprocess.run(["make", "-C", NATIVE], check=True,
                   capture_output=True)

    driver = ClusterDriver(
        cfg, args.replicas, workdir=wd, app_ports=ports,
        timeout_cfg=TimeoutConfig(elec_timeout_low=0.5,
                                  elec_timeout_high=1.0),
        fanout=args.fanout, sync_period=args.sync_period,
        pipeline=args.pipeline_depth, scan=bool(args.scan))
    if args.scan:
        from rdma_paxos_tpu.runtime.sim import cap_scan_tiers
        try:
            cap_scan_tiers(driver.cluster, args.scan)
        except ValueError as e:
            raise SystemExit(f"--scan: {e}")
    apps = []
    for r, port in enumerate(ports):
        env = dict(os.environ)
        env["LD_PRELOAD"] = os.path.join(NATIVE, "interpose.so")
        env["RP_PROXY_SOCK"] = os.path.join(wd, f"proxy{r}.sock")
        apps.append(subprocess.Popen(
            [os.path.join(SRC, "redis-server"), "--port", str(port),
             "--bind", "127.0.0.1", "--save", "", "--appendonly", "no"],
            env=env, stdout=subprocess.DEVNULL,
            stderr=subprocess.DEVNULL))
    for port in ports:
        while True:
            try:
                socket.create_connection(("127.0.0.1", port),
                                         timeout=2).close()
                break
            except OSError:
                time.sleep(0.1)
    stats = None
    if args.profile:
        # direct wall-time phase accounting on the poll thread (cProfile
        # mis-attributes C-level waits under load): wraps the driver's
        # major sub-phases with monotonic sums
        stats = {"iters": 0, "step_wall": 0.0, "device": 0.0,
                 "replay_fetch": 0.0, "apply": 0.0, "sync": 0.0,
                 "loop_wall": [None, None]}

        def timed(obj, name, key):
            orig = getattr(obj, name)

            def wrap(*a, **kw):
                t0 = time.monotonic()
                try:
                    return orig(*a, **kw)
                finally:
                    stats[key] += time.monotonic() - t0
            setattr(obj, name, wrap)

        timed(driver.cluster, "step", "device")
        timed(driver.cluster, "step_burst", "device")
        timed(driver.cluster, "_replay_committed", "replay_fetch")
        timed(driver, "_apply_new_entries", "apply")
        for rt in driver.runtimes:
            if rt.store is not None:
                timed(rt.store, "sync", "sync")
        orig_step = driver.step

        def stat_step():
            if stats["loop_wall"][0] is None:
                stats["loop_wall"][0] = time.monotonic()
            t0 = time.monotonic()
            try:
                return orig_step()
            finally:
                now = time.monotonic()
                stats["step_wall"] += now - t0
                stats["iters"] += 1
                stats["loop_wall"][1] = now
        driver.step = stat_step
    print("prewarming step/burst compiles...")
    driver.prewarm()
    # idle heartbeat cadence 20 ms (event arrival wakes the loop
    # instantly): on a shared-core host the loop must not busy-poll the
    # CPU away from the app it serves
    driver.run(period=0.02)
    t0 = time.time()
    while driver.leader() < 0:
        time.sleep(0.05)
        if time.time() - t0 > 120:
            raise SystemExit("no leader elected")
    lead = driver.leader()
    print(f"leader: replica {lead} (redis on port {ports[lead]})")

    # the reference's client (run.sh:73-82), with pipelining
    def bench_round():
        cmd = [os.path.join(SRC, "redis-benchmark"), "-p",
               str(ports[lead]), "-t", "set", "-n", str(args.n),
               "-c", str(args.c), "-P", str(args.P)]
        if args.r:
            cmd += ["-r", str(args.r)]
        bench = subprocess.run(cmd, capture_output=True, timeout=600)
        out = bench.stdout.decode()
        rps_r = None
        for l in out.splitlines():
            if "requests per second" in l:
                try:
                    rps_r = float(l.split()[0].strip('"'))
                except ValueError:
                    pass
        return rps_r, out

    from benchmarks.reporting import (
        ab_pipeline_rounds, phase_accumulate, phase_snapshot)

    main_phases: dict = {}
    pre = phase_snapshot(driver)
    rps, out = bench_round()
    phase_accumulate(driver, pre, main_phases)
    print("\n".join(l for l in out.splitlines()
                    if "requests per second" in l or "SET" in l))

    ab_host = None
    if args.scan and args.ab_hostpath > 0:
        # host-path A/B on the REFERENCE headline workload: scalar
        # per-entry host loops + no scan vs the vectorized data plane
        # + K-window scan tier (alternating best-of, same core)
        from benchmarks.reporting import ab_variant_rounds
        from rdma_paxos_tpu.runtime import hostpath as hostpath_mod

        def apply_variant(on: bool):
            hostpath_mod.set_vectorized(on)
            driver.cluster.scan = on

        ab_host = ab_variant_rounds(driver, args.ab_hostpath,
                                    apply_variant,
                                    lambda: bench_round()[0])
        if ab_host["off"] and ab_host["on"]:
            print(f"host-path A/B: {ab_host['off']:.0f} SET/s scalar "
                  f"vs {ab_host['on']:.0f} SET/s vectorized+scan -> "
                  f"{ab_host['on'] / ab_host['off']:.2f}x")

    ab = None
    if args.ab_pipeline > 0 and args.pipeline_depth >= 2:
        # pipeline on/off A/B on the SAME core, same day — alternating
        # best-of rounds (the --audit overhead methodology); the
        # in-flight-depth counter proves the ON rounds overlapped,
        # per-variant phase attribution
        ab = ab_pipeline_rounds(driver, args.ab_pipeline,
                                args.pipeline_depth,
                                lambda: bench_round()[0])
        if ab["off"] and ab["on"]:
            print(f"pipeline A/B: {ab['off']:.0f} SET/s off vs "
                  f"{ab['on']:.0f} SET/s on -> "
                  f"{ab['on'] / ab['off']:.2f}x "
                  f"(max in-flight dispatches {ab['depth_seen']})")

    # follower state equality, the run.sh FindLeader+verify analog
    time.sleep(2.0)
    followers_equal = True
    lead_size = resp(ports[lead], b"DBSIZE")
    for r in range(args.replicas):
        if r == lead:
            continue
        deadline = time.time() + 30
        size = None
        while time.time() < deadline:
            size = resp(ports[r], b"DBSIZE")
            if size == lead_size:
                break
            time.sleep(0.5)
        followers_equal = followers_equal and size == lead_size
        print(f"replica {r} DBSIZE {size.decode()} "
              f"(leader {lead_size.decode()})"
              + ("  OK" if size == lead_size else "  MISMATCH"))

    driver.stop()
    from benchmarks.reporting import emit
    emit("redis_set_ops_per_sec", rps, "ops/s",
         detail=dict(replicas=args.replicas, n=args.n, c=args.c,
                     P=args.P, r=args.r, fanout=args.fanout,
                     pipeline_depth=args.pipeline_depth,
                     followers_equal=followers_equal,
                     phases=dict(sorted(main_phases.items())),
                     leader_dbsize=int(lead_size.lstrip(b":") or 0)),
         obs=driver.obs)
    if ab_host is not None and ab_host["off"] and ab_host["on"]:
        emit("host_path_speedup",
             round(ab_host["on"] / ab_host["off"], 3), "x",
             detail=dict(off_ops_per_sec=ab_host["off"],
                         on_ops_per_sec=ab_host["on"],
                         off_us_per_op=round(1e6 / ab_host["off"], 2),
                         on_us_per_op=round(1e6 / ab_host["on"], 2),
                         rounds=args.ab_hostpath,
                         n_per_round=args.n,
                         scan_k=max(driver.cluster.K_TIERS),
                         scan_dispatches=int(
                             driver.cluster.scan_dispatches),
                         shared_core_caveat=(
                             "alternating best-of on shared CPU "
                             "cores"),
                         phases_on=ab_host["phases_on"],
                         phases_off=ab_host["phases_off"]),
             obs=driver.obs)
    if ab is not None and ab["off"] and ab["on"]:
        emit("pipeline_speedup", round(ab["on"] / ab["off"], 3), "x",
             detail=dict(off_ops_per_sec=ab["off"],
                         on_ops_per_sec=ab["on"],
                         rounds=args.ab_pipeline,
                         n_per_round=args.n,
                         pipeline_depth=args.pipeline_depth,
                         max_inflight_dispatches=ab["depth_seen"],
                         phases_on=ab["phases_on"],
                         phases_off=ab["phases_off"]),
             obs=driver.obs)
    if stats is not None:
        lw = (stats["loop_wall"][1] - stats["loop_wall"][0]
              if stats["loop_wall"][0] is not None else 0.0)
        print(f"phase stats: iters={stats['iters']} "
              f"loop_wall={lw:.2f}s step_wall={stats['step_wall']:.2f}s "
              f"device={stats['device']:.2f}s "
              f"(of which replay_fetch={stats['replay_fetch']:.2f}s) "
              f"apply={stats['apply']:.2f}s sync={stats['sync']:.2f}s "
              f"idle={lw - stats['step_wall']:.2f}s")
    for a in apps:
        a.kill()
        a.wait()


if __name__ == "__main__":
    main()
