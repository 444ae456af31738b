"""What the deployment kinds share: they all stand on
``rdma_paxos_tpu.runtime.driver.ClusterDriver``, so the probe of its
counters, the leader/term view, the host phase events and the span the
benchmark records round ``SimCluster._fetch_all`` are one piece of code.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional, Tuple


def log_config(geometry: dict):
    from rdma_paxos_tpu.config import LogConfig
    return LogConfig(n_slots=geometry["n_slots"],
                     slot_bytes=geometry["slot_bytes"],
                     window_slots=geometry["window_slots"],
                     batch_slots=geometry["batch_slots"])


def check_shipped_timers(timers: dict) -> None:
    """The benchmark passes the driver no timers, so it runs the ones
    the program ships; a configuration file that states others would
    lie about what was measured."""
    from rdma_paxos_tpu.config import TimeoutConfig
    t = TimeoutConfig()
    shipped = dict(heartbeat_s=t.hb_period,
                   election_low_s=t.elec_timeout_low,
                   election_high_s=t.elec_timeout_high)
    if timers != shipped:
        raise RuntimeError(
            f"configuration states timers {timers}, the program ships "
            f"{shipped}")


class SpanAcc:
    """(count, total_us) of a span the benchmark records itself."""

    def __init__(self):
        self.count, self.total_us = 0, 0.0

    def add(self, t0_ns: int) -> None:
        self.count += 1
        self.total_us += (time.perf_counter_ns() - t0_ns) / 1e3


class DriverDeployment:
    """Base of the deployment kinds over a ``ClusterDriver``."""

    # the probe counter that counts device dispatches (steps and bursts)
    DISPATCH_COUNTER = "phase.device_dispatch.count"

    def __init__(self, config: dict, ctx):
        self.config, self.ctx = config, ctx
        self.geometry = (config["rehearsal_geometry"] if ctx.rehearsal
                         else config["geometry"])
        self.cfg = log_config(self.geometry)
        check_shipped_timers(config["timers"])
        self.R = int(config["replicas"])
        self.spmd = config["mapping"]["kind"] == "replica_per_chip"
        self.driver = None
        self.stable_since = 0.0     # when boot saw its leader
        self.bench_spans: Dict[str, SpanAcc] = {"replay_fetch": SpanAcc()}

    # ---- life cycle -------------------------------------------------

    def driver_kwargs(self) -> dict:
        kw = dict(self.config.get("driver_options", {}))
        if self.spmd:
            kw["mode"] = "spmd"
        return kw

    def boot(self) -> None:
        """prewarm (compile, or load from the cache), run, first leader.

        Only the programs the cell's traffic runs: both step variants
        and the burst tiers the configuration names, not the driver's
        whole ladder. They are loaded BEFORE the loop starts: a program
        loaded while serving stalls the dispatch thread for longer than
        the shipped election timers, and leadership moves. The toy
        geometry of the rehearsal reaches higher tiers, so it loads all.
        """
        t0 = time.monotonic()
        tiers = (None if self.ctx.rehearsal
                 else self.config["prewarm_burst_tiers"])
        self.driver.cluster.prewarm(tiers=tiers)
        self.ctx.part("prewarm_compile_or_cache_load", t0)
        t0 = time.monotonic()
        self.driver.run()
        deadline = time.monotonic() + 120
        while self.driver.leader() < 0:
            self.raise_if_dead()
            if time.monotonic() > deadline:
                raise RuntimeError("no leader elected within 120 s")
            time.sleep(0.01)
        # load follows at once, and the harness watches the leader and
        # term from here on. Waiting here would change what is measured:
        # left idle, a freshly booted group on the chip elects a third
        # time within half a second (PERF.md, Findings, PR 25)
        self.stable_since = time.monotonic()
        self.ctx.part("election", t0)
        placement = self.log_devices()
        self.ctx.say("deploy", f"leader=replica {self.driver.leader()} "
                     f"log rows on {placement}")
        if self.spmd and len(set(placement)) != len(placement):
            raise RuntimeError(
                f"replicas share a chip: {placement} (want one each)")

    def inject(self, fault: str) -> None:
        """``election_under_warm_up``: a second into the first bring-up
        a follower times out on the leader once, as after a stall of the
        host (the loop's next burst is a single step that carries the
        time-out): the group elects again under the generator, and the
        harness has to notice and build everything anew (the second
        bring-up is left alone)."""
        if fault != "election_under_warm_up":
            raise SystemExit(f"perfbench: unknown fault {fault!r}")
        if fault in self.ctx.injected:
            return
        self.ctx.injected.add(fault)
        victim = next(r for r in range(self.R) if r != self.driver.leader())
        cluster = self.driver.cluster

        def arm():
            def timed_out_once(**_kw):
                del cluster.step_burst      # the class's method again
                return cluster.step(timeouts=[victim])
            cluster.step_burst = timed_out_once
            self.ctx.say("fault", f"{fault}: replica {victim} times out "
                         f"on the leader at the next dispatch")
        t = threading.Timer(1.0, arm)
        t.daemon = True
        t.start()

    def stop(self) -> None:
        if self.driver is not None:
            self.driver.stop()
            self.driver = None

    def raise_if_dead(self) -> None:
        if self.driver.loop_error is not None:
            raise RuntimeError(
                f"driver loop died: {self.driver.loop_error!r}")

    # ---- views ------------------------------------------------------

    def log_devices(self) -> List[str]:
        c = self.driver.cluster
        out = [None] * c.R
        for shard in c.state.log.buf.addressable_shards:
            for r in range(*shard.index[0].indices(c.R)):
                out[r] = str(shard.device)
        return out

    def leader_term(self) -> Tuple[int, int]:
        last = self.driver.cluster.last
        lead = self.driver.leader()
        if last is None or lead < 0:
            return (-1, -1)
        return (lead, int(last["term"][lead]))

    def leader_device_id(self) -> Optional[int]:
        if not self.spmd:
            return None
        lead = max(self.driver.leader(), 0)
        return int(self.driver.cluster.replica_device(lead).id)

    def shapes(self) -> dict:
        from rdma_paxos_tpu.consensus.log import META_W
        return dict(replicas=self.R,
                    entry_bytes=self.cfg.slot_bytes + 4 * META_W,
                    chips_holding_state=self.R if self.spmd else 1)

    def probe(self) -> Dict[str, float]:
        """Monotone counters of the program, read at the window's two
        ends: the phase profiler's exact (count, total) pairs, the
        metrics registry's counters summed over their labels, the
        highest term, the spans the benchmark records itself."""
        out: Dict[str, float] = {}
        acc: dict = {}
        for _ in range(3):
            try:
                acc = dict(self.driver._phase_prof.acc)
                break
            except RuntimeError:        # a phase's first sample landed
                continue
        for phase, (n, total, _mx) in acc.items():
            out[f"phase.{phase}.count"] = n
            out[f"phase.{phase}.total_us"] = total
        for key, v in self.driver.obs.metrics.snapshot()["counters"].items():
            name = "counter." + key.split("{", 1)[0]
            out[name] = out.get(name, 0) + v
        out.setdefault("counter.election_timeouts_total", 0)
        last = self.driver.cluster.last
        out["term"] = int(last["term"].max()) if last is not None else -1
        out["protocol_steps"] = int(self.driver.cluster.step_index)
        for name, sp in self.bench_spans.items():
            out[f"bench.{name}.count"] = sp.count
            out[f"bench.{name}.total_us"] = sp.total_us
        return out

    def enable_tracing(self) -> None:
        """Traced runs only: host phase events on the monotonic clock,
        and a span round the replay fetch (no profiler phase covers it):
        from the fetch program's dispatch to its rows being on the host.
        The program binds the fetch under its host lock and reads the
        result outside it; the span must not move the blocking read
        under the lock, so it ends inside the result's own conversion."""
        import numpy as np
        self.driver._phase_prof.enable_events(capacity=1 << 20)
        cluster = self.driver.cluster
        fetch, span = cluster._fetch_all, self.bench_spans["replay_fetch"]

        class Fetched:
            def __init__(self, arr, t0_ns=None):
                self.arr, self.t0_ns = arr, t0_ns

            def __array__(self, dtype=None, copy=None):
                out = np.asarray(self.arr)
                if self.t0_ns is not None:
                    span.add(self.t0_ns)
                return out

        def timed_fetch(log, starts):
            t0 = time.perf_counter_ns()
            wd, wm = fetch(log, starts)
            return Fetched(wd), Fetched(wm, t0)     # wm is read last
        cluster._fetch_all = timed_fetch

    def host_phase_events(self) -> list:
        ev = self.driver._phase_prof.events
        return list(ev) if ev is not None else []

    def memory_peak_bytes(self) -> Optional[int]:
        import jax
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in jax.devices()]
        peaks = [p for p in peaks if p is not None]
        return max(peaks) if peaks else None
