#!/usr/bin/env python
"""Reconfiguration benchmark — the ``benchmarks/reconf_bench.sh`` analog.

Scenarios under continuous client load (timings printed like the
reference's ``timer_start/stop`` around re-election,
``reconf_bench.sh:17-25,248-300``):

  remove-leader    — partition the leader; measure time to a new leader
                     and to the first committed write after failover
  remove-follower  — partition a follower; verify commit continues
  add-server       — joint-consensus upsize under load
  evict            — auto-eviction of the dead follower

    python benchmarks/reconf_bench.py [--json RECONF.json]
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

os.environ.setdefault(
    "XLA_FLAGS", "--xla_force_host_platform_device_count=8")
import jax  # noqa: E402

if os.environ.get("RP_BENCH_CPU", "1") == "1":
    jax.config.update("jax_platforms", "cpu")

from rdma_paxos_tpu.utils.compile_cache import (  # noqa: E402
    use_compile_cache)

use_compile_cache()

from rdma_paxos_tpu.config import LogConfig, TimeoutConfig  # noqa: E402
from rdma_paxos_tpu.consensus.state import Role  # noqa: E402
from rdma_paxos_tpu.runtime.driver import ClusterDriver  # noqa: E402

CFG = LogConfig(n_slots=1024, slot_bytes=128, window_slots=64,
                batch_slots=64)


def drive_until(driver, cond, timeout=240.0, load_replica=None, counter=[0]):
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < timeout:
        if load_replica is not None and load_replica() >= 0:
            counter[0] += 1
            driver.cluster.submit(load_replica(), b"load-%d" % counter[0])
        driver.step()
        if cond():
            return time.perf_counter() - t0
    raise TimeoutError


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default=None,
                    help="write timings as a JSON artifact")
    args = ap.parse_args()
    out = {"metric": "reconfiguration_timings",
           "backend": None, "scenarios": {}}
    # Election timeouts must exceed the per-step cost or timers fire on
    # every iteration and leadership never settles. One profile for
    # every backend; ROADMAP S6 re-derives it from chip data.
    tcfg = TimeoutConfig(elec_timeout_low=0.05, elec_timeout_high=0.15)
    d = ClusterDriver(CFG, 8, group_size=5,
                      timeout_cfg=tcfg,
                      auto_evict=False, fail_threshold=30)
    d.prewarm()          # compiles out of the timed windows
    d.cluster.run_until_elected(0)
    drive_until(d, lambda: d.leader() >= 0)
    lead = d.leader()
    print(f"boot: leader={lead}, group=5 (of 8-replica mesh)")

    # --- RemoveLeader ---
    d.cluster.partition([[lead], [r for r in range(8) if r != lead]])
    t = drive_until(d, lambda: d.leader() not in (-1, lead),
                    load_replica=lambda: -1)
    new_lead = d.leader()
    print(f"remove-leader: new leader {new_lead} in {t * 1e3:.0f} ms")
    out["scenarios"]["remove_leader_new_leader_ms"] = round(t * 1e3, 1)
    base = int(d.cluster.last["commit"][new_lead])
    d.cluster.submit(new_lead, b"first-after-failover")
    t = drive_until(
        d, lambda: int(d.cluster.last["commit"][new_lead]) > base)
    print(f"remove-leader: first commit after failover +{t * 1e3:.0f} ms")
    out["scenarios"]["remove_leader_first_commit_ms"] = round(t * 1e3, 1)

    # --- RemoveFollower under load ---
    d.cluster.heal()
    d.step()
    fol = next(r for r in range(5) if r != new_lead and r != lead)
    d.cluster.partition([[x for x in range(8) if x != fol], [fol]])
    base = int(d.cluster.last["commit"][new_lead])
    t = drive_until(
        d, lambda: int(d.cluster.last["commit"][new_lead]) >= base + 50,
        load_replica=lambda: d.leader())
    print(f"remove-follower: 50 commits under failure in {t * 1e3:.0f} ms "
          f"(no interruption)")
    out["scenarios"]["remove_follower_50_commits_ms"] = round(t * 1e3, 1)

    # --- AddServer (upsize 5 -> 7) under load ---
    d.cluster.heal()
    drive_until(d, lambda: d.leader() >= 0)   # settle post-heal elections
    cur_lead = d.leader()
    d.request_membership(0b1111111)
    t = drive_until(
        d, lambda: d._mm.current(cur_lead)["bitmask_new"] == 0b1111111
        and d._config_phase is None,
        load_replica=lambda: d.leader())
    print(f"add-server: upsize 5->7 committed in {t * 1e3:.0f} ms "
          f"under load")
    out["scenarios"]["add_server_upsize_ms"] = round(t * 1e3, 1)

    # --- Evict a dead member ---
    d.auto_evict = True
    d.cluster.partition([[x for x in range(8) if x != 6], [6]])
    t = drive_until(
        d, lambda: not (d._mm.current(d.leader())["bitmask_new"] >> 6) & 1
        if d.leader() >= 0 else False,
        load_replica=lambda: d.leader(), timeout=120)
    print(f"evict: dead member removed in {t * 1e3:.0f} ms")
    out["scenarios"]["evict_dead_member_ms"] = round(t * 1e3, 1)

    d.stop()
    print("all scenarios OK")
    out["backend"] = jax.default_backend()
    out["config"] = dict(n_slots=CFG.n_slots, slot_bytes=CFG.slot_bytes,
                         window_slots=CFG.window_slots,
                         batch_slots=CFG.batch_slots, replicas=8,
                         group_size=5)
    out["notes"] = (
        "in-process driver timings (the reference's reconf_bench.sh "
        "timer_start/stop contract, :17-25); election timeouts "
        "50-150 ms.")
    if args.json:
        with open(args.json, "w") as f:
            json.dump(out, f, indent=2)
    from benchmarks.reporting import emit
    emit("reconfiguration_timings",
         out["scenarios"].get("remove_leader_new_leader_ms"), "ms",
         detail=dict(backend=out["backend"],
                     scenarios=out["scenarios"],
                     config=out["config"]),
         obs=d.obs)


if __name__ == "__main__":
    main()
