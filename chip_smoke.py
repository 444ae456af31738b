#!/usr/bin/env python3
"""chip_smoke.py — prove the served path starts and answers on the TPU.

One process owns the chip and drives, through the entry points a user
calls, the path users pay for: client -> interposed app or KVS session
-> ClusterDriver -> compiled replica step -> readback -> apply -> ack,
at the reference's deployment size (64 MiB of payload ring per replica,
``dare_log.h:76``; a 4M-slot device KVS table per replica).

Phases (each checks its answers against a plain dict):

* ``set``      — 3 replicas, 3 ``native/toyserver`` apps under
  ``LD_PRELOAD=native/interpose.so``, 10,000 pipelined SETs through the
  leader's app; COUNT and a seeded GET sample on ALL THREE apps.
* ``kvs``      — the device-resident KVS over a second driver (the other
  window fan-out): >= 20,000 keys through ``ClientSession``, read back
  linearizably on the leader and by read-index on both followers.
* ``variants`` — the audit/telemetry/scan/txn step variants and the
  sharded engine: compile, elect, commit one entry.
* ``spmd_*``   — with >= 3 devices: ``set`` and ``kvs`` again with one
  replica per chip (three distinct chips must hold the logs AND the
  tables), then a 2x2 ``(group, replica)`` mesh step and burst.

Exits non-zero unless JAX reports a TPU; ``--rehearse-cpu`` runs every
phase at a toy geometry on 4 virtual CPU devices (Pallas interpreted)
and says so in its summary. The summary (per-phase counts) is the
``[summary]`` line; the LAST stdout line is exactly
``{"ok": ..., "device": {"platform": ..., "kind": ..., "count": ...}}``,
the object the driver's chip check parses. Times it prints are set-up
information, not measurements.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import shutil
import socket
import subprocess
import sys
import tempfile
import threading
import time
import traceback

REPO = os.path.dirname(os.path.abspath(__file__))
NATIVE = os.path.join(REPO, "native")

HARD_LIMIT_S = 1170         # the driver allows 1200 s; never hang past it
SAMPLE = 200                # seeded read-back sample per replica
SET_CONNS, SET_PIPELINE = 4, 16

_children: list = []        # every process this script started


def _kill_children() -> None:
    for p in _children:
        if p.poll() is None:
            p.kill()
    for p in _children:
        p.wait()
    _children.clear()


class Geometry:
    """Deployment sizes: the reference's, or the CPU rehearsal's toy."""

    def __init__(self, rehearsal: bool):
        from rdma_paxos_tpu.config import LogConfig
        if rehearsal:
            self.cfg = LogConfig(n_slots=1024, slot_bytes=128,
                                 window_slots=64, batch_slots=64)
            self.n_set, self.kvs_cap = 400, 1 << 12
            self.kvs_min, self.kvs_sessions, self.kvs_load_s = 200, 50, 0.0
        else:
            # 131072 x 512 B = 64 MiB payload ring per replica; window
            # and batch are REDIS_r05's
            self.cfg = LogConfig(n_slots=131072, slot_bytes=512,
                                 window_slots=1024, batch_slots=1024)
            self.n_set, self.kvs_cap = 10_000, 1 << 22
            self.kvs_min, self.kvs_sessions, self.kvs_load_s = (
                20_000, 500, 45.0)


# ---------------------------------------------------------------------------
# shared helpers
# ---------------------------------------------------------------------------

def wait_leader(driver, timeout: float = 120.0) -> int:
    deadline = time.time() + timeout
    while driver.leader() < 0:
        if driver.loop_error is not None:
            raise RuntimeError(f"driver loop died: {driver.loop_error!r}")
        if time.time() > deadline:
            raise RuntimeError(f"no leader elected within {timeout:.0f}s")
        time.sleep(0.02)
    return driver.leader()


def log_devices(cluster) -> list:
    """Per replica, the device holding its log rows."""
    out = [None] * cluster.R
    for shard in cluster.state.log.buf.addressable_shards:
        rows = range(*shard.index[0].indices(cluster.R))
        for r in rows:
            out[r] = str(shard.device)
    return out


def dispatch_count(driver) -> int:
    """Device dispatches the driver issued (steps and bursts), from its
    own step-phase histogram."""
    hists = driver.obs.metrics.snapshot()["histograms"]
    return sum(h["count"] for name, h in hists.items()
               if name.startswith("step_phase_us{")
               and "phase=device_dispatch" in name)


def check_placement(what: str, devices: list) -> None:
    if len(set(devices)) != len(devices):
        raise RuntimeError(
            f"{what}: replicas share a chip — {devices} (want one each)")


def free_ports(n: int) -> list:
    socks = [socket.socket() for _ in range(n)]
    try:
        for s in socks:
            s.bind(("127.0.0.1", 0))
        return [s.getsockname()[1] for s in socks]
    finally:
        for s in socks:
            s.close()


# ---------------------------------------------------------------------------
# phase: set — the reference's headline deployment
# ---------------------------------------------------------------------------

def set_client(port: int, tid: int, n: int, seed: int, acked: dict,
               errors: list) -> None:
    """One pipelined connection: SET_PIPELINE commands per write (the
    app reads them as one buffer, so they ride one consensus event). A
    severed connection (an event refused during leadership churn)
    reconnects and re-issues the same batch, bounded; a key enters
    ``acked`` only when its +OK arrived."""
    try:
        rng = random.Random(f"set:{seed}:{tid}")
        retries = 5
        s = socket.create_connection(("127.0.0.1", port), timeout=60)
        f = s.makefile("rb")
        done = 0
        while done < n:
            batch = [(b"k%d-%d" % (tid, done + i),
                      b"v%d" % rng.randrange(1 << 30))
                     for i in range(min(SET_PIPELINE, n - done))]
            while True:
                try:
                    s.sendall(b"".join(b"SET %s %s\n" % kv for kv in batch))
                    for k, v in batch:
                        if f.readline().strip() != b"+OK":
                            raise OSError("severed mid-batch")
                        acked[k] = v
                    break
                except OSError:
                    if retries <= 0:
                        raise
                    retries -= 1
                    s.close()
                    time.sleep(0.2)
                    s = socket.create_connection(("127.0.0.1", port),
                                                 timeout=60)
                    f = s.makefile("rb")
            done += len(batch)
        s.close()
    except Exception as exc:  # noqa: BLE001 — surfaced by the phase
        errors.append((tid, exc))


def app_cmd(port: int, lines: list) -> list:
    with socket.create_connection(("127.0.0.1", port), timeout=60) as s:
        f = s.makefile("rb")
        out = []
        for ln in lines:
            s.sendall(ln + b"\n")
            out.append(f.readline().strip())
        return out


def phase_set(geo: Geometry, seed: int, spmd: bool) -> dict:
    from rdma_paxos_tpu.runtime.driver import ClusterDriver

    wd = tempfile.mkdtemp(prefix="chip_smoke_")
    ports = free_ports(3)
    driver = ClusterDriver(geo.cfg, 3, workdir=wd, app_ports=ports,
                           fanout="psum",
                           **({"mode": "spmd"} if spmd else {}))
    try:
        for r, port in enumerate(ports):
            env = dict(os.environ,
                       LD_PRELOAD=os.path.join(NATIVE, "interpose.so"),
                       RP_PROXY_SOCK=os.path.join(wd, f"proxy{r}.sock"))
            _children.append(subprocess.Popen(
                [os.path.join(NATIVE, "toyserver"), str(port)], env=env,
                stderr=subprocess.DEVNULL))
        time.sleep(0.3)                     # let the apps bind
        if any(p.poll() is not None for p in _children):
            raise RuntimeError("a toyserver exited at start-up")
        driver.prewarm()
        driver.run()
        lead = wait_leader(driver)
        placement = log_devices(driver.cluster)
        print(f"[set] leader=replica {lead} log rows on {placement}",
              flush=True)
        if spmd:
            check_placement("log rows", placement)

        acked: dict = {}
        errors: list = []
        per = geo.n_set // SET_CONNS
        threads = [threading.Thread(
            target=set_client,
            args=(ports[lead], t, per, seed, acked, errors))
            for t in range(SET_CONNS)]
        t0 = time.time()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        load_s = time.time() - t0
        if errors:
            raise RuntimeError(f"SET clients failed: {errors!r}")
        if len(acked) != per * SET_CONNS:
            raise RuntimeError(
                f"{len(acked)} of {per * SET_CONNS} SETs acknowledged")

        # every acknowledged write must be in EVERY replica's app: wait
        # (bounded) for the followers' apply frontier, then read back
        deadline = time.time() + 60
        while True:
            counts = [int(app_cmd(p, [b"COUNT"])[0]) for p in ports]
            if all(c == len(acked) for c in counts):
                break
            if time.time() > deadline:
                raise RuntimeError(
                    f"apps hold {counts} keys, {len(acked)} acknowledged")
            time.sleep(0.2)
        sample = random.Random(f"sample:{seed}").sample(
            sorted(acked), min(SAMPLE, len(acked)))
        verified = []
        for r, port in enumerate(ports):
            got = app_cmd(port, [b"GET " + k for k in sample])
            bad = [k for k, g in zip(sample, got) if g != acked[k]]
            if bad:
                raise RuntimeError(
                    f"replica {r}'s app disagrees on {len(bad)} of "
                    f"{len(sample)} sampled keys, e.g. {bad[0]!r}")
            verified.append(len(sample))
        if driver.loop_error is not None:
            raise RuntimeError(f"driver loop died: {driver.loop_error!r}")
        c = driver.cluster
        return dict(acknowledged=len(acked), app_counts=counts,
                    verified=verified, load_seconds=round(load_s, 2),
                    dispatches=dispatch_count(driver),
                    protocol_steps=int(c.step_index),
                    max_inflight_dispatches=int(c.max_inflight_dispatches),
                    term=int(c.last["term"].max()),
                    log_devices=placement)
    finally:
        driver.stop()
        _kill_children()
        shutil.rmtree(wd, ignore_errors=True)


# ---------------------------------------------------------------------------
# phase: kvs — the device-resident state machine
# ---------------------------------------------------------------------------

def phase_kvs(geo: Geometry, seed: int, spmd: bool) -> dict:
    from rdma_paxos_tpu.models.replicated_kvs import ReplicatedKVS
    from rdma_paxos_tpu.runtime.driver import ClusterDriver

    driver = ClusterDriver(geo.cfg, 3,
                           **({"mode": "spmd"} if spmd else {}))
    kv = ReplicatedKVS(driver.cluster, cap=geo.kvs_cap)
    try:
        driver.prewarm()
        driver.run()
        lead = wait_leader(driver)
        logs = log_devices(driver.cluster)
        tables = [str(next(iter(t.keys.devices()))) for t in kv.tables]
        print(f"[kvs] leader=replica {lead} log rows on {logs} "
              f"tables on {tables}", flush=True)
        if spmd:
            check_placement("log rows", logs)
            check_placement("KVS tables", tables)
            if logs != tables:
                raise RuntimeError(
                    f"tables {tables} are not on their logs' chips {logs}")

        def read(fn, replica, timeout=300.0):
            """One queued linearizable read; ``fn`` runs at the
            linearization point on the driver's readback side."""
            t = driver.read(fn, replica=replica, timeout=timeout)
            if t.status != "ok":
                raise RuntimeError(
                    f"read at replica {replica}: status={t.status!r}")
            return t

        rng = random.Random(f"kvs:{seed}")
        sessions = [kv.session(i + 1) for i in range(geo.kvs_sessions)]
        model: dict = {}
        t0 = time.time()
        while (len(model) < geo.kvs_min
               or time.time() - t0 < geo.kvs_load_s):
            # one request outstanding per session (the ClientSession
            # contract): a round of puts, then wait until a linearizable
            # read at the leader returns every one of them
            round_kv = {}
            for sess in sessions:
                key = b"key%07d" % (len(model) + len(round_kv))
                round_kv[key] = b"%x" % rng.getrandbits(96)
                sess.put(lead, key, round_kv[key])
            keys = list(round_kv)
            deadline = time.time() + 120
            while True:
                got = read(lambda: kv.get_many(lead, keys), lead).value
                if all(g == round_kv[k] for k, g in zip(keys, got)):
                    break
                if driver.leader() != lead:
                    raise RuntimeError("leadership moved during the load")
                if time.time() > deadline:
                    missing = sum(g != round_kv[k]
                                  for k, g in zip(keys, got))
                    raise RuntimeError(
                        f"{missing} puts of a round never became readable")
            model.update(round_kv)
        load_s = time.time() - t0

        sample = random.Random(f"sample:{seed}").sample(
            sorted(model), min(SAMPLE, len(model)))
        verified, paths = [0] * 3, [None] * 3
        for r in range(3):
            if r == lead:
                t = read(lambda: [kv.get(lead, k, linearizable=True)
                                  for k in sample], lead)
            else:
                # the hub confirmed the read index and waited for this
                # follower's apply frontier before it calls us
                t = read(lambda r=r: [kv.serve_local(r, k)
                                      for k in sample], r)
            bad = [k for k, g in zip(sample, t.value) if g != model[k]]
            if bad:
                raise RuntimeError(
                    f"replica {r}'s table disagrees on {len(bad)} of "
                    f"{len(sample)} sampled keys, e.g. {bad[0]!r}")
            verified[r], paths[r] = len(sample), t.path
        if driver.loop_error is not None:
            raise RuntimeError(f"driver loop died: {driver.loop_error!r}")
        c = driver.cluster
        return dict(keys=len(model),
                    load_factor=round(len(model) / geo.kvs_cap, 5),
                    table_slots=geo.kvs_cap, verified=verified,
                    read_paths=paths, load_seconds=round(load_s, 2),
                    deduped=list(kv.deduped),
                    dispatches=dispatch_count(driver),
                    protocol_steps=int(c.step_index),
                    term=int(c.last["term"].max()),
                    log_devices=logs, table_devices=tables)
    finally:
        driver.stop()


# ---------------------------------------------------------------------------
# phase: variants — device programs the chip has not compiled before
# ---------------------------------------------------------------------------

def phase_variants(geo: Geometry, pallas_kw: dict) -> dict:
    from rdma_paxos_tpu.runtime.sim import SimCluster
    from rdma_paxos_tpu.shard.cluster import ShardedCluster

    sim = SimCluster(geo.cfg, 3, audit=True, telemetry=True, scan=True,
                     txn=True, fanout="psum", **pallas_kw)
    sim.prewarm(tiers=(2,))
    sim.run_until_elected(0)
    sim.submit(0, b"SET variant 1")
    sim.step()
    res = sim.step()
    if int(res["commit"].min()) < 2:        # NOOP + the entry, everywhere
        raise RuntimeError(f"variants: commit stuck at {res['commit']}")
    if sim.auditor.findings:
        raise RuntimeError(f"audit findings: {sim.auditor.findings!r}")

    shard = ShardedCluster(geo.cfg, 3, 2, txn=True, **pallas_kw)
    shard.prewarm(tiers=(2,))
    shard.place_leaders()
    for g in range(2):
        shard.submit(g, shard.leader(g), b"SET variant %d" % g)
    shard.step()
    gres = shard.step()
    if int(gres["commit"].min()) < 2:
        raise RuntimeError(f"sharded: commit stuck at {gres['commit']}")
    return dict(sim_commit=res["commit"].tolist(),
                sharded_commit=gres["commit"].tolist(),
                sharded_dispatches=int(shard.dispatches))


def phase_mesh2x2(geo: Geometry, pallas_kw: dict) -> dict:
    from rdma_paxos_tpu.shard.cluster import ShardedCluster

    shard = ShardedCluster(geo.cfg, 2, 2, mesh=(2, 2), **pallas_kw)
    devices = [[str(d) for d in row] for row in shard.mesh.devices]
    print(f"[mesh2x2] (group, replica) devices {devices}", flush=True)
    shard.place_leaders()
    for g in range(2):
        shard.submit(g, shard.leader(g), b"SET mesh %d" % g)
    shard.step()
    res = shard.step()
    if int(res["commit"].min()) < 2:
        raise RuntimeError(f"mesh2x2: commit stuck at {res['commit']}")
    for g in range(2):
        shard.submit(g, shard.leader(g), b"SET burst %d" % g)
    shard.step_burst()
    res = shard.step()
    if int(res["commit"].min()) < 3:
        raise RuntimeError(f"mesh2x2 burst: commit at {res['commit']}")
    return dict(commit=res["commit"].tolist(), devices=devices,
                dispatches=int(shard.dispatches))


# ---------------------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="rehearse every phase on 4 virtual CPU devices "
                         "at a toy geometry (not a chip check)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=4")

    def hard_stop():
        print("chip_smoke: hard time limit reached, aborting",
              file=sys.stderr, flush=True)
        _kill_children()
        os._exit(3)
    watchdog = threading.Timer(HARD_LIMIT_S, hard_stop)
    watchdog.daemon = True
    watchdog.start()

    # ---- phase 0: preflight ----
    import jax
    from rdma_paxos_tpu.runtime.sim import STEP_CACHE
    from rdma_paxos_tpu.utils.compile_cache import use_compile_cache
    cache_dir = use_compile_cache()
    dev = jax.devices()[0]
    device = dict(platform=dev.platform, kind=dev.device_kind,
                  count=len(jax.devices()))
    print(f"[preflight] jax {jax.__version__} platform={dev.platform} "
          f"device_kind={dev.device_kind!r} devices={device['count']} "
          f"compile_cache={cache_dir}", flush=True)
    if dev.platform != "tpu" and not args.rehearse_cpu:
        print(f"chip_smoke: needs a TPU, JAX found platform "
              f"{dev.platform!r} ({dev.device_kind!r}); "
              f"--rehearse-cpu rehearses on the CPU", file=sys.stderr)
        return 2

    compile_s = [0.0]

    def on_duration(event, secs, **_kw):
        # compile_or_get_cached: a persistent-cache hit is counted at
        # its (small) load time, so a warm run reports the difference
        if event == "/jax/core/compile/backend_compile_duration":
            compile_s[0] += secs
    jax.monitoring.register_event_duration_secs_listener(on_duration)

    geo = Geometry(args.rehearse_cpu)
    pallas_kw = (dict(use_pallas=True, interpret=True)
                 if args.rehearse_cpu else {})
    phases: dict = {}

    def run_phase(name, fn, *a):
        print(f"[{name}] start", flush=True)
        t0, c0 = time.time(), compile_s[0]
        try:
            row = dict(ok=True, **fn(*a))
        except Exception as exc:  # noqa: BLE001 — recorded, fails the run
            traceback.print_exc()
            row = dict(ok=False, error=f"{type(exc).__name__}: {exc}"[:500])
        row.update(seconds=round(time.time() - t0, 2),
                   compile_seconds=round(compile_s[0] - c0, 2),
                   programs_in_step_cache=len(STEP_CACHE))
        phases[name] = row
        print(f"[{name}] {json.dumps(row)}", flush=True)

    # ---- phase 1: build from source (git ignores the binaries, the
    # chip tool copies the disk — never test a stale interpose.so) ----
    def build():
        subprocess.run(["make", "-C", NATIVE, "clean", "all"], check=True,
                       capture_output=True, timeout=300)
        return {}
    run_phase("build", build)

    run_phase("set", phase_set, geo, args.seed, False)
    run_phase("kvs", phase_kvs, geo, args.seed, False)
    run_phase("variants", phase_variants, geo, pallas_kw)
    if device["count"] >= 3:
        run_phase("spmd_set", phase_set, geo, args.seed, True)
        run_phase("spmd_kvs", phase_kvs, geo, args.seed, True)
    else:
        phases["spmd_set"] = phases["spmd_kvs"] = dict(
            skipped=f"{device['count']} device")
    if device["count"] >= 4:
        run_phase("mesh2x2", phase_mesh2x2, geo, pallas_kw)
    else:
        phases["mesh2x2"] = dict(skipped=f"{device['count']} device")

    ok = all(p["ok"] for p in phases.values() if "skipped" not in p)
    mem = dev.memory_stats() or {}
    summary = dict(ok=ok, device=device, phases=phases,
                   peak_device_bytes=mem.get("peak_bytes_in_use"),
                   compile_cache=cache_dir,
                   compile_seconds=round(compile_s[0], 2), reduced=[])
    if args.rehearse_cpu:
        summary.update(rehearsal=True, platform="cpu")
    watchdog.cancel()
    print(f"[summary] {json.dumps(summary)}", flush=True)
    # the result line: these two keys and nothing else
    print(json.dumps(dict(ok=ok, device=device)), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
