"""One run: set-up, settle, window, check, result line.

The process that runs this owns the chip. It builds the cell's
deployment, starts the cell's generator as warm-up, opens the window
only on a settled system, sleeps through it, closes it, stops the
generator, checks the answers and prints the contract's JSON line last.
While the window is open this thread does nothing but sleep: whatever
runs is the program under test or the generator.
"""

from __future__ import annotations

import gc
import json
import os
import shutil
import sys
import tempfile
import threading
import time
import traceback
from typing import Dict, List, Optional

from perfbench.harness import native, spec, trace
from perfbench.harness.metrics import read_all
from perfbench.harness.sample import longest_gap

HARD_LIMIT_S = 1170         # the driver allows a compiling run 1200 s
TERM_SETTLED_S = 2.0
COMPILE_QUIET_S = 1.0
TRACE_CAP_S = 6.0           # a traced run's window: the trace is large
SETTLE_TIMEOUT_S = 240.0
BRING_UP_TRIES = 4
WINDOW_TRIES = 3


class LeadershipMoved(RuntimeError):
    """The group elected again between the generator's start and the
    window's opening: the generator's connections are to a deposed
    leader's app, which serves them locally and replicates nothing (or,
    with requests in flight, is quarantined). Nothing measured on them
    would be the replicated path; the deployment is built anew."""


class RunContext:
    """What deployments, generators and readers are handed."""

    def __init__(self, args, cell, t_start: float):
        self.cell = cell
        self.seed, self.seconds = args.seed, float(args.seconds)
        self.traced = bool(args.trace)
        self.rehearsal = bool(args.rehearse_cpu)
        self.t_start = t_start
        self.tmp_root = tempfile.mkdtemp(prefix="perfbench_")
        self.workdir = self.tmp_root    # one below it per bring-up
        self.children: list = []        # every process this run started
        self.injected: set = set()      # faults that fire once a run
        self.setup_parts: Dict[str, float] = {}
        self.compile_events: List[float] = []   # monotonic stamps
        self.compile_seconds = 0.0
        self.device_kind: Optional[str] = None

    def say(self, tag: str, msg: str) -> None:
        print(f"[{tag}] {msg}", flush=True)

    def part(self, name: str, t0: float) -> None:
        self.setup_parts[name] = (self.setup_parts.get(name, 0.0)
                                  + time.monotonic() - t0)

    def out_dir(self) -> str:
        d = os.path.join(spec.ROOT, "chiprun_out", "perfbench",
                         f"{self.cell.name}.seed{self.seed}"
                         f".trace{int(self.traced)}."
                         + time.strftime("%H%M%S"))
        os.makedirs(d, exist_ok=True)
        return d

    def kill_children(self) -> None:
        for p in self.children:
            if p.poll() is None:
                p.kill()
        for p in self.children:
            p.wait()
        self.children.clear()


def preflight(ctx: RunContext) -> dict:
    """Fix the compile cache, look for the chip; -> the ``device``
    object, or exit non-zero naming what was found."""
    import jax

    from rdma_paxos_tpu.utils.compile_cache import use_compile_cache
    cache_dir = use_compile_cache()
    devs = jax.devices()
    dev = devs[0]
    ctx.device_kind = dev.device_kind
    ctx.say("preflight",
            f"jax {jax.__version__} platform={dev.platform} "
            f"device_kind={dev.device_kind!r} devices={len(devs)} "
            f"compile_cache={cache_dir}")
    if not ctx.rehearsal:
        if dev.platform != "tpu":
            raise SystemExit(
                f"perfbench: needs a TPU, JAX found platform "
                f"{dev.platform!r} ({dev.device_kind!r}); "
                f"--rehearse-cpu rehearses on the CPU")
        if dev.device_kind not in ctx.cell.peaks:
            raise SystemExit(
                f"perfbench: device kind {dev.device_kind!r} is not in "
                f"perfbench/peaks.json (known: {sorted(ctx.cell.peaks)})")
        if len(devs) < ctx.cell.chips:
            raise SystemExit(
                f"perfbench: workload {ctx.cell.name!r} needs "
                f"{ctx.cell.chips} chips, JAX found {len(devs)}")

    def on_duration(event, secs, **_kw):
        # fires for a fresh compile and for a persistent-cache load
        if event == "/jax/core/compile/backend_compile_duration":
            ctx.compile_events.append(time.monotonic())
            ctx.compile_seconds += secs
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    return dict(platform=dev.platform, kind=dev.device_kind,
                count=len(devs))


def settle(ctx: RunContext, deployment, generator, aimed) -> None:
    """Warm-up is the cell's own traffic. Return once the leader and
    term the generator was ``aimed`` at have stood for TERM_SETTLED_S,
    the mix's ``warmup_ops`` operations have completed and nothing has
    compiled for COMPILE_QUIET_S. Any other leader or term in between
    is a ``LeadershipMoved``."""
    want = int(ctx.cell.traffic["warmup_ops"])
    deadline = time.monotonic() + SETTLE_TIMEOUT_S
    while True:
        now = time.monotonic()
        view = deployment.leader_term()
        if view != aimed:
            raise LeadershipMoved(
                f"the generator was started on leader/term {aimed}, the "
                f"group now says {view}")
        stood = now - deployment.stable_since
        done = generator.completed()
        quiet = (not ctx.compile_events
                 or now - ctx.compile_events[-1] >= COMPILE_QUIET_S)
        if stood >= TERM_SETTLED_S and done >= want and quiet:
            ctx.say("settle", f"leader=replica {view[0]} term={view[1]} "
                    f"stood {stood:.2f}s, {done} warm-up "
                    f"operations, {len(ctx.compile_events)} programs "
                    f"compiled or loaded so far")
            return
        deployment.raise_if_dead()
        if now > deadline:
            raise RuntimeError(
                f"never settled: leader/term {view} for "
                f"{stood:.1f}s, {done}/{want} warm-up operations")
        time.sleep(0.05)


def bring_up(ctx: RunContext, args):
    """-> (deployment, generator, capture or None, the leader and term
    they stand on): the cell's deployment, serving the cell's own
    traffic from the leader's app, settled, the trace (if any) started:
    ready for the window. Where the group elects again on the way there
    (a stall of this host longer than the election timers is enough:
    every replica's timer runs on this host's clock), everything is
    stopped and built again; it all counts as set-up."""
    cell = ctx.cell
    for attempt in range(1, BRING_UP_TRIES + 1):
        ctx.workdir = tempfile.mkdtemp(prefix="up", dir=ctx.tmp_root)
        deployment = spec.component(
            "deployments", cell.config["deployment"]).build(cell.config,
                                                            ctx)
        try:
            deployment.start()
            if args.fault:
                deployment.inject(args.fault)
            if ctx.traced:
                deployment.enable_tracing()
            t0 = time.monotonic()
            aimed = deployment.leader_term()
            cap = generator = None
            if aimed[0] < 0:
                raise LeadershipMoved("the group is electing as the "
                                      "generator is to start")
            generator = spec.component(
                "generators", cell.traffic["generator"]).build(
                    cell.traffic, deployment, ctx)
            generator.start()
            settle(ctx, deployment, generator, aimed)
            gc.collect()
            gc.freeze()
            ctx.part("warm_up", t0)
            if ctx.traced:
                cap = trace.Capture(
                    os.path.join(ctx.workdir, "prof")).start()
            view = deployment.leader_term()
            if view != aimed:
                raise LeadershipMoved(
                    f"settled on leader/term {aimed}, the group says "
                    f"{view} as the window is to open")
            return deployment, generator, cap, aimed
        except LeadershipMoved as exc:
            ctx.say("settle", f"bring-up {attempt} of {BRING_UP_TRIES}: "
                    f"{exc}; stopping it and building the deployment "
                    f"again")
            if cap is not None:
                cap.stop()
            gc.unfreeze()
            deployment.stop()
            ctx.kill_children()
            del deployment, generator
            gc.collect()
            ctx.part("bring_up_again", t0)
        except BaseException:
            deployment.stop()
            raise
    raise RuntimeError(f"leadership moved under the warm-up in each of "
                       f"{BRING_UP_TRIES} bring-ups")


def run(args, t_start: float) -> int:
    cell = spec.Cell(args.workload)
    ctx = RunContext(args, cell, t_start)

    def hard_stop():
        print("perfbench: hard time limit reached, aborting",
              file=sys.stderr, flush=True)
        ctx.kill_children()
        os._exit(3)
    watchdog = threading.Timer(HARD_LIMIT_S, hard_stop)
    watchdog.daemon = True
    watchdog.start()

    deployment = None
    try:
        t0 = time.monotonic()
        native.ensure_built(spec.ROOT)
        ctx.part("native_build", t0)
        t0 = time.monotonic()
        device = preflight(ctx)
        ctx.part("jax_import_devices", t0)

        seconds = (min(ctx.seconds, TRACE_CAP_S) if ctx.traced
                   else ctx.seconds)
        for n_window in range(1, WINDOW_TRIES + 1):
            deployment, generator, cap, aimed = bring_up(ctx, args)

            # ---- the window ----
            n_compiled = len(ctx.compile_events)
            probe_open = deployment.probe()
            t_open = time.monotonic()
            setup_s = t_open - ctx.t_start
            ctx.say("setup", json.dumps(dict(
                setup_s=setup_s, compile_or_load_s=ctx.compile_seconds,
                parts={k: round(v, 3)
                       for k, v in ctx.setup_parts.items()})))
            time.sleep(seconds)
            t_close = time.monotonic()
            if cap is not None:
                cap.close_window()
            view = deployment.leader_term()
            if view == aimed:
                break
            # the group elected inside the window: the generator's
            # sessions were severed and the deposed leader's app is
            # quarantined, so what the window holds is a failover and
            # not this cell's traffic; nothing of it is reported
            ctx.say("window", f"window {n_window} of {WINDOW_TRIES} "
                    f"DISCARDED after {t_close - t_open:.1f}s: opened on "
                    f"leader/term {aimed}, closed on {view}; election "
                    f"timeouts so far "
                    f"{deployment.probe()['counter.election_timeouts_total']}"
                    f"; stopping everything and building it again (all "
                    f"of this counts as set-up)")
            t0 = time.monotonic()
            if cap is not None:
                cap.stop()
            gc.unfreeze()
            deployment.stop()
            deployment = None
            ctx.kill_children()
            del generator
            gc.collect()
            ctx.part("window_discarded", t0 - (t_close - t_open))
        else:
            raise RuntimeError(f"leadership moved inside each of "
                               f"{WINDOW_TRIES} windows")
        probe_close = deployment.probe()
        compiled_in_window = len(ctx.compile_events) - n_compiled
        sample = generator.stop(t_open, t_close)
        gc.unfreeze()

        # ---- outside the window ----
        gap_s, gap_at = longest_gap(sample.all_completions, t_open, t_close)
        elections = dict(
            timeouts=(probe_close.get("counter.election_timeouts_total", 0)
                      - probe_open.get("counter.election_timeouts_total",
                                       0)),
            term_open=probe_open.get("term"),
            term_close=probe_close.get("term"))
        ctx.say("window", json.dumps(dict(
            seconds=t_close - t_open, operations=len(sample.completions),
            attempted=sample.attempted, failed=sample.failed,
            longest_gap_between_completions_s=gap_s,
            longest_gap_at_s=gap_at, elections_in_window=elections,
            compilations_in_window=compiled_in_window,
            steady_state=(compiled_in_window == 0),
            generator=sample.report)))
        if compiled_in_window:
            ctx.say("window", "NOT in steady state: something compiled "
                    "inside the window")
        with open(os.path.join(ctx.out_dir(), "timeline.json"), "w") as f:
            json.dump(dict(t_open=t_open, t_close=t_close,
                           completions=[t - t_open for t in
                                        sample.all_completions],
                           probe_open=probe_open, probe_close=probe_close),
                      f)

        checks = deployment.check(sample, ctx.seed)
        correct = True
        for c in checks:
            ctx.say("check", json.dumps(c))
            correct = correct and bool(c["ok"])
        if not correct:
            # on stderr too: the end of it is what a refusal quotes
            print("perfbench: NOT correct: "
                  + "; ".join(f"{c['what']}: got {c['got']}, want "
                              f"{c['want']}" for c in checks
                              if not c["ok"])
                  + f"; leader/term at the window's start {aimed}, now "
                  f"{deployment.leader_term()}; generator "
                  f"{sample.report}", file=sys.stderr, flush=True)

        reduced = None
        if cap is not None:
            # only now: writing the trace out freezes this process for
            # seconds, the election timers fire when it thaws, and a
            # deposed leader's app may refuse the check's questions
            t0 = time.monotonic()
            cap.stop()
            ctx.say("trace", f"stopped and written in "
                    f"{time.monotonic() - t0:.2f}s; leader/term now "
                    f"{deployment.leader_term()}")
            t0 = time.monotonic()
            reduced = trace.reduce_trace(
                cap.xplane_path(), cap.anchors,
                deployment.host_phase_events(), rehearsal=ctx.rehearsal)
            ctx.say("trace", f"{os.path.getsize(cap.xplane_path())} bytes "
                    f"reduced in {time.monotonic() - t0:.1f}s: window "
                    f"{reduced['window_s']:.3f}s, busy "
                    f"{reduced['busy_s']:.3f}s on "
                    f"{len(reduced['devices'])} device(s); programs "
                    + json.dumps({k: round(v, 6) for k, v in sorted(
                        reduced["module_seconds"].items(),
                        key=lambda kv: -kv[1])[:8]}))

        view = dict(ctx=ctx, cell=cell, sample=sample, t_open=t_open,
                    t_close=t_close, window_s=t_close - t_open,
                    setup_s=setup_s, probe_open=probe_open,
                    probe_close=probe_close, trace=reduced,
                    deployment=deployment, device=device)
        metrics = read_all(cell.per_layer() if ctx.traced
                           else cell.end_to_end(), view)
        peaks = deployment.memory_peak_bytes()
        device["memory_peak_bytes"] = peaks
        result = dict(correct=correct, attempted=sample.attempted,
                      failed=sample.failed, metrics=metrics, device=device)
        if reduced is not None:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            result["breakdown"] = dict(device_ops=reduced["device_ops"],
                                       idle_gaps=reduced["idle_gaps"])
        if n_window > 1:
            result["windows_discarded"] = n_window - 1
        if ctx.rehearsal:
            result["rehearsal"] = True
        deployment.stop()
        deployment = None
        ctx.kill_children()
        watchdog.cancel()
        print(json.dumps(result), flush=True)
        return 0
    except SystemExit:
        raise
    except BaseException:  # noqa: BLE001 — no result line on any failure
        traceback.print_exc()
        return 1
    finally:
        try:
            if deployment is not None:
                deployment.stop()
        finally:
            ctx.kill_children()
            shutil.rmtree(ctx.tmp_root, ignore_errors=True)
