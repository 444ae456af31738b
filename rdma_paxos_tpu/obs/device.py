"""Device telemetry — on-device protocol counters, profiler-correlated
dispatch timelines, and per-variant compiled-program cost reports.

Every observability layer before this one stops at the dispatch
boundary: the jit-safety rule keeps metrics/trace/span calls out of
compiled code, so the step program is a black box — elections,
quorum widths, link-model drops, and log occupancy are only ever
*inferred* from host-side outputs, and the one device-time signal
(``fence=``) perturbs the very pipeline it measures. Replication
offload work makes the same point (PAPERS.md: SmartNIC replication,
arXiv:2503.18093; RDMA agreement, arXiv:1905.12143): once the protocol
hot path moves off the host, the telemetry must move with it. Three
legs, mirroring that split:

* **On-device counters** (``telemetry=True`` compiled steps,
  ``consensus/step.py``): a compact u32 vector per replica per step —
  elections started, votes granted/denied, appends accepted,
  commit-frontier advance, link-model drops consumed, effective
  quorum width, log headroom — reduced in-program so readback is
  O(counters), never O(log). The engines ingest the vector on the
  PR 6 readback thread (``finish``) into the metrics registry as
  ``device_*{replica=,group=}`` series and into a host accumulator
  (:func:`zeros` / :func:`accumulate`) tests can assert exactly.
  ``telemetry=False`` programs and STEP_CACHE keys are bit-identical
  to the pre-telemetry world (cache-key guarded like ``fence=`` and
  ``audit=``; ``tests/test_device_obs.py``).

* **:class:`ProfilerSession`** — a bounded ``jax.profiler`` capture
  manager (driver API / ``run_bench --profile`` / alert-triggered).
  The profiler's Chrome-trace output stamps event ``ts`` as
  microseconds since ``start_trace``; the session records
  ``time.time()`` immediately before starting, so device events
  project onto the shared :mod:`~rdma_paxos_tpu.obs.clock` wall
  timebase exactly — :func:`merge_timeline` folds them into the span
  export as one Perfetto document: client span → host phases →
  actual device execution.

* **:func:`program_report`** — per-STEP_CACHE-variant compiled-program
  cost: ``lowered.compile().cost_analysis()`` flops / bytes accessed
  plus ``memory_analysis()`` argument/output/temp sizes, emitted as a
  ``program_report.json`` artifact and a BENCH row.

Layout contract: :data:`COUNTERS` + :data:`GAUGES` name the vector
columns IN ORDER. ``consensus/step.py`` carries its own matching
``T_*`` index constants — it must NOT import this module (the static
jit-safety scan pins profiler/registry symbols unreachable from
compiled code); ``tests/test_device_obs.py`` pins the two layouts
against each other instead.

HARD RULE (inherited from the rest of ``obs``): nothing here runs
inside jitted/``shard_map``ped code. JAX is imported lazily (profiler
and program-report paths only) so the module stays importable from
any host layer.
"""

from __future__ import annotations

import argparse
import glob
import gzip
import json
import os
import sys
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from rdma_paxos_tpu.obs.clock import anchor as clock_anchor

# ---------------------------------------------------------------------------
# counter-vector layout (mirrors consensus/step.py T_* — pinned by test)
# ---------------------------------------------------------------------------

# monotone per-step counts: accumulated (summed) across steps/bursts
COUNTERS = (
    "elections_started",    # this replica began a candidacy
    "votes_granted",        # granted another replica's candidacy
    "votes_denied",         # heard candidacies it did not grant
    "accepted_entries",     # client entries appended from the batch
    "committed_entries",    # commit-frontier advance
    "links_unheard",        # peers masked by partition/link model
)
# point-in-time values: latest step wins (min across a fused burst
# for log_headroom — the tightest the ring got inside the dispatch)
GAUGES = (
    "quorum_width",         # replicas that acked this replica's window
    "log_headroom",         # free ring slots: (n_slots-1) - (end-head)
)
NAMES: Tuple[str, ...] = COUNTERS + GAUGES
WIDTH = len(NAMES)
INDEX: Dict[str, int] = {n: i for i, n in enumerate(NAMES)}

_N_COUNTERS = len(COUNTERS)
_I_QUORUM = INDEX["quorum_width"]
_I_HEADROOM = INDEX["log_headroom"]


def zeros(*lead_shape: int) -> np.ndarray:
    """The host-side telemetry accumulator: int64 ``[..., WIDTH]``."""
    return np.zeros(tuple(lead_shape) + (WIDTH,), np.int64)


def reduce_steps(stacked: np.ndarray) -> np.ndarray:
    """Reduce a fused burst's per-step vectors ``[K, ..., WIDTH]`` to
    one ``[..., WIDTH]`` vector: counters sum over the K steps,
    ``quorum_width`` takes the final step's value, ``log_headroom``
    the minimum across the burst (the tightest the ring got)."""
    out = stacked.sum(axis=0).astype(np.int64)
    out[..., _I_QUORUM] = stacked[-1, ..., _I_QUORUM]
    out[..., _I_HEADROOM] = stacked[..., _I_HEADROOM].min(axis=0)
    return out


def accumulate(acc: np.ndarray, vec: np.ndarray) -> np.ndarray:
    """Fold one finish()'s reduced vector into the running host
    accumulator: counter columns add, gauge columns overwrite."""
    acc[..., :_N_COUNTERS] += vec[..., :_N_COUNTERS]
    acc[..., _N_COUNTERS:] = vec[..., _N_COUNTERS:]
    return acc


def export(metrics, vec: np.ndarray, *, replica: int,
           group: Optional[int] = None) -> None:
    """Push one replica's reduced vector into the registry:
    ``device_<counter>_total`` counters (incremented by this finish's
    delta) and ``device_<gauge>`` gauges, labelled ``{replica=}`` (+
    ``{group=}`` for sharded engines). Host-side only — runs on the
    readback thread, never inside compiled code."""
    labels = dict(replica=replica)
    if group is not None:
        labels["group"] = group
    for i, name in enumerate(COUNTERS):
        v = int(vec[i])
        if v:
            metrics.inc("device_%s_total" % name, v, **labels)
    for name in GAUGES:
        metrics.set("device_%s" % name, int(vec[INDEX[name]]), **labels)


def ingest(obs, vec: np.ndarray, *, group_offset: int = 0) -> None:
    """Registry export for a whole reduced vector array: ``[R, WIDTH]``
    (single group) or ``[G, R, WIDTH]`` (sharded — ``group_offset``
    shifts the group label for multi-host shards)."""
    if obs is None:
        return
    m = obs.metrics
    if vec.ndim == 2:
        for r in range(vec.shape[0]):
            export(m, vec[r], replica=r)
    else:
        for g in range(vec.shape[0]):
            for r in range(vec.shape[1]):
                export(m, vec[g, r], replica=r, group=g + group_offset)


# ---------------------------------------------------------------------------
# jax.profiler capture manager
# ---------------------------------------------------------------------------

# jax.profiler allows ONE active trace per process; the session guards
# that invariant so driver/CLI/alert triggers can race benignly
_ACTIVE_LOCK = threading.Lock()
_ACTIVE: Optional["ProfilerSession"] = None


class ProfilerSession:
    """A bounded ``jax.profiler`` capture whose device trace aligns
    onto the shared obs wall timebase.

    The profiler's Chrome-trace output stamps event ``ts`` in
    microseconds since the ``start_trace`` call, so the session
    records ``time.time()`` immediately before starting:
    ``wall = wall_start + ts * 1e-6`` projects every device event onto
    the same timebase span dumps use (:mod:`obs.clock`). ``stop()`` is
    explicit; :meth:`maybe_stop` enforces ``max_seconds`` from a host
    poll loop (the driver calls it each observe pass) so an
    alert-triggered capture can never run unbounded."""

    def __init__(self, log_dir: str, *, max_seconds: float = 10.0):
        self.log_dir = log_dir
        self.max_seconds = float(max_seconds)
        self.active = False
        self.wall_start: Optional[float] = None
        self.anchor = None
        self.trace_files: List[str] = []
        self._deadline = float("inf")

    def start(self) -> "ProfilerSession":
        global _ACTIVE
        import jax
        with _ACTIVE_LOCK:
            if _ACTIVE is not None and _ACTIVE.active:
                raise RuntimeError(
                    "a ProfilerSession is already active (jax allows "
                    "one trace per process); stop it first")
            os.makedirs(self.log_dir, exist_ok=True)
            self.anchor = clock_anchor()
            self.wall_start = time.time()
            self._deadline = time.monotonic() + self.max_seconds
            jax.profiler.start_trace(self.log_dir)
            self.active = True
            _ACTIVE = self
        return self

    def expired(self) -> bool:
        return self.active and time.monotonic() >= self._deadline

    def maybe_stop(self) -> bool:
        """Stop iff the bounded duration elapsed (poll-loop hook)."""
        if self.expired():
            self.stop()
            return True
        return False

    def stop(self) -> "ProfilerSession":
        global _ACTIVE
        with _ACTIVE_LOCK:
            if not self.active:
                return self
            import jax
            try:
                jax.profiler.stop_trace()
            finally:
                # even when trace serialization fails (disk full in
                # log_dir), the session must read inactive and release
                # the one-per-process slot — otherwise every later
                # maybe_stop/start_profile retries against a wedged
                # trace instead of reporting this one's error
                self.active = False
                if _ACTIVE is self:
                    _ACTIVE = None
            # resolve INSIDE the lock: a concurrent stop() returns on
            # the not-active fast path above only after the files are
            # populated, so its caller never reads an empty capture
            self.trace_files = sorted(glob.glob(
                os.path.join(self.log_dir, "**", "*.trace.json.gz"),
                recursive=True))
        return self

    def chrome_events(self) -> List[dict]:
        """The captured raw Chrome trace events (``ts`` µs since
        :attr:`wall_start`), concatenated across trace files. Empty
        when the capture produced none (or was never stopped)."""
        events: List[dict] = []
        for path in self.trace_files:
            with gzip.open(path, "rt") as f:
                doc = json.load(f)
            events.extend(doc.get("traceEvents", []))
        return events

    def summary(self) -> dict:
        return dict(log_dir=self.log_dir, active=self.active,
                    wall_start=self.wall_start,
                    max_seconds=self.max_seconds,
                    trace_files=list(self.trace_files))


def load_profiler_dir(log_dir: str) -> List[dict]:
    """Raw Chrome events from a previously captured profiler log dir
    (the CLI path — no live session needed)."""
    s = ProfilerSession(log_dir)
    s.trace_files = sorted(glob.glob(
        os.path.join(log_dir, "**", "*.trace.json.gz"), recursive=True))
    return s.chrome_events()


# ---------------------------------------------------------------------------
# merged Perfetto timeline: spans + host phases + device trace
# ---------------------------------------------------------------------------

HOST_PHASE_PID = 9998        # one below the spans critical-path pid
DEVICE_PID_BASE = 10000      # profiler pids are remapped above here
# a busy capture emits millions of runtime events; an uncapped merge
# writes a multi-hundred-MB JSON no viewer loads. The newest events
# (the serving window, not the capture-init preamble) are kept; the
# drop count lands in otherData — bounded, never silently complete.
MAX_DEVICE_EVENTS = 200_000


def _span_walls(dumps: Sequence[dict]) -> List[float]:
    walls: List[float] = []
    for d in dumps:
        a = d["anchor"]
        for sp in d["spans"]:
            walls.extend(a["wall"] + (ts - a["monotonic"])
                         for _, _, ts in sp["events"])
    return walls


def merge_timeline(span_dumps, *, phase_events: Optional[Sequence] = None,
                   phase_anchor: Optional[dict] = None,
                   profiler: Optional[ProfilerSession] = None,
                   device_events: Optional[Sequence[dict]] = None,
                   device_wall_start: Optional[float] = None,
                   max_cp_tracks: int = 512,
                   max_device_events: int = MAX_DEVICE_EVENTS) -> dict:
    """One Perfetto document on ONE wall timebase: the span export's
    replica + critical-path tracks, a ``host phases`` track from the
    :class:`~rdma_paxos_tpu.obs.spans.StepPhaseProfiler` event ring
    (``(phase, t0_monotonic, t1_monotonic)`` triples projected through
    ``phase_anchor``), and the profiler's device-execution tracks
    (``ts`` µs since the capture's ``wall_start``). Every source
    contributes to the common epoch, so the three layers line up —
    a client span's quorum wait sits directly above the host dispatch
    phase and the device program that served it."""
    from rdma_paxos_tpu.obs import spans as spans_mod

    if isinstance(span_dumps, dict):
        span_dumps = [span_dumps]
    span_dumps = list(span_dumps or [])
    phase_events = list(phase_events or [])
    if profiler is not None:
        device_events = profiler.chrome_events()
        device_wall_start = profiler.wall_start
    device_events = [e for e in (device_events or [])
                     if e.get("ph") in ("X", "M")]

    pa = phase_anchor if phase_anchor is not None else clock_anchor()
    walls = _span_walls(span_dumps)
    walls.extend(pa["wall"] + (t0 - pa["monotonic"])
                 for _, t0, _ in phase_events)
    if device_events and device_wall_start is not None:
        walls.append(device_wall_start)
    t0_wall = min(walls) if walls else 0.0

    doc = spans_mod.to_chrome_trace(span_dumps, t0_wall=t0_wall,
                                    max_cp_tracks=max_cp_tracks)
    events = doc["traceEvents"]

    def us(w: float) -> float:
        return round((w - t0_wall) * 1e6, 3)

    # host-phase track: one thread row per phase name
    if phase_events:
        tids = {p: i + 1
                for i, p in enumerate(sorted({p for p, _, _
                                              in phase_events}))}
        events.append(dict(name="process_name", ph="M",
                           pid=HOST_PHASE_PID, tid=0,
                           args=dict(name="host phases")))
        for p, tid in sorted(tids.items()):
            events.append(dict(name="thread_name", ph="M",
                               pid=HOST_PHASE_PID, tid=tid,
                               args=dict(name=p)))
        for p, m0, m1 in phase_events:
            w0 = pa["wall"] + (m0 - pa["monotonic"])
            w1 = pa["wall"] + (m1 - pa["monotonic"])
            events.append(dict(
                name=p, ph="X", ts=us(w0),
                dur=round(max(w1 - w0, 0.0) * 1e6, 3),
                pid=HOST_PHASE_PID, tid=tids[p], args={}))

    # device tracks: profiler pids remapped above DEVICE_PID_BASE so
    # they can never collide with replica / critical-path / phase pids
    n_dev = 0
    dev_dropped = 0
    if device_events and device_wall_start is not None:
        xs = [e for e in device_events if e.get("ph") == "X"]
        if len(xs) > max_device_events:
            # keep the NEWEST slices (the serving window) and say so.
            # Chrome traces are ordered per thread/file, NOT globally
            # by time — sort first or the tail-slice drops whole
            # device tracks instead of the capture-init preamble
            xs.sort(key=lambda e: e.get("ts", 0))
            dev_dropped = len(xs) - max_device_events
            keep = xs[-max_device_events:]
            device_events = ([e for e in device_events
                              if e.get("ph") == "M"] + keep)
        pid_map: Dict[int, int] = {}
        for e in device_events:
            pid = pid_map.setdefault(
                e.get("pid", 0), DEVICE_PID_BASE + len(pid_map))
            ne = dict(e)
            ne["pid"] = pid
            if e.get("ph") == "M":
                if e.get("name") == "process_name":
                    ne["args"] = dict(name="device: %s"
                                      % e.get("args", {}).get("name", "?"))
                events.append(ne)
                continue
            ne["ts"] = us(device_wall_start + e["ts"] * 1e-6)
            events.append(ne)
            n_dev += 1

    doc["otherData"]["merged"] = True
    doc["otherData"]["host_phase_events"] = len(phase_events)
    doc["otherData"]["device_events"] = n_dev
    doc["otherData"]["device_events_dropped"] = dev_dropped
    return doc


# ---------------------------------------------------------------------------
# per-variant compiled-program cost reports
# ---------------------------------------------------------------------------

def _example_args(cluster, K: int = 1):
    """An abstract (state, packed argument) pair shaped for a K-step
    dispatch of ``cluster`` (a single step is K = 1) — the prewarm
    shapes, which are exactly what the serving path dispatches. Both
    are ``ShapeDtypeStruct``s, so lowering never touches live device
    buffers (safe to run while the driver loop keeps dispatching —
    donation cannot invalidate an abstract aval)."""
    import jax
    import jax.numpy as jnp

    from rdma_paxos_tpu.consensus.step import arg_layout

    G = getattr(cluster, "G", None)
    lead = (G, cluster.R) if G is not None else (cluster.R,)
    state = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), cluster.state)
    lay = arg_layout(cluster.cfg, cluster.R, K,
                     bool(getattr(cluster, "_txn", False)) and K == 1)
    return state, jax.ShapeDtypeStruct(lay.shape(lead), jnp.int32)


def _analyze(lowered) -> dict:
    """flops / bytes-accessed / memory sizes of one compiled variant
    (best-effort: backends may omit pieces of the analysis)."""
    out: dict = {}
    try:
        compiled = lowered.compile()
    except Exception as exc:  # noqa: BLE001 — report, don't crash
        return dict(error=repr(exc))
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        if ca:
            out["flops"] = float(ca.get("flops", 0.0))
            out["bytes_accessed"] = float(ca.get("bytes accessed", 0.0))
    except Exception:  # noqa: BLE001
        pass
    try:
        ma = compiled.memory_analysis()
        mem = {}
        for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                     "temp_size_in_bytes", "alias_size_in_bytes",
                     "generated_code_size_in_bytes"):
            v = getattr(ma, attr, None)
            if v is not None:
                mem[attr] = int(v)
        peak = (mem.get("argument_size_in_bytes", 0)
                + mem.get("output_size_in_bytes", 0)
                + mem.get("temp_size_in_bytes", 0))
        mem["peak_bytes"] = peak
        out["memory"] = mem
    except Exception:  # noqa: BLE001
        pass
    return out


def program_report(cluster, *, tiers: Sequence[int] = ()) -> dict:
    """Cost/memory report for every step variant this cluster serves
    (full + stable step, plus the requested fused-burst tiers) —
    the static complement of the runtime counters: what one dispatch
    COSTS, per STEP_CACHE variant. Lowering reuses the live state's
    shapes; nothing is executed or donated."""
    import jax

    cfg = cluster.cfg
    variants = []
    for elections in (True, False):
        fn, _ = cluster._program("step", elections=elections)
        row = dict(variant=("step/full" if elections else "step/stable"))
        row.update(_analyze(fn.lower(*_example_args(cluster))))
        variants.append(row)
    for K in tiers:
        fn, _ = cluster._program("burst", K)
        row = dict(variant="burst/K=%d" % K)
        row.update(_analyze(fn.lower(*_example_args(cluster, K))))
        variants.append(row)
    return dict(
        schema=1, kind="program_report", anchor=clock_anchor(),
        backend=jax.default_backend(),
        engine=getattr(cluster, "_mode", "sim"),
        n_replicas=cluster.R,
        n_groups=getattr(cluster, "G", 1),
        config=dict(n_slots=cfg.n_slots, slot_bytes=cfg.slot_bytes,
                    window_slots=cfg.window_slots,
                    batch_slots=cfg.batch_slots),
        telemetry=bool(getattr(cluster, "_telemetry", False)),
        audit=bool(getattr(cluster, "_audit", False)),
        variants=variants)


def write_program_report(path: str, cluster, *,
                         tiers: Sequence[int] = ()) -> dict:
    """Atomic ``program_report.json`` artifact next to the bench
    outputs; returns the report dict."""
    rep = program_report(cluster, tiers=tiers)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(rep, f, indent=2)
    os.replace(tmp, path)
    rep["path"] = path
    return rep


# ---------------------------------------------------------------------------
# CLI: merge a profiler capture + span dumps into one Perfetto file
# ---------------------------------------------------------------------------

def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m rdma_paxos_tpu.obs.device",
        description="Merge a jax.profiler capture dir and span dumps "
                    "into ONE Perfetto timeline on the shared clock "
                    "anchors.")
    sub = ap.add_subparsers(dest="cmd", required=True)
    mp = sub.add_parser("merge", help="write the merged Perfetto JSON")
    mp.add_argument("--profile-dir", default=None,
                    help="a ProfilerSession log dir (trace.json.gz "
                         "inside)")
    mp.add_argument("--wall-start", type=float, default=None,
                    help="the capture's wall_start (time.time() at "
                         "start_trace) — required with --profile-dir")
    mp.add_argument("--spans", nargs="*", default=[],
                    help="raw span dump JSONs")
    mp.add_argument("-o", "--out", required=True)
    args = ap.parse_args(argv)

    dumps = []
    for p in args.spans:
        with open(p) as f:
            dumps.append(json.load(f))
    dev_events = None
    if args.profile_dir:
        if args.wall_start is None:
            raise SystemExit("--profile-dir requires --wall-start "
                             "(the capture's start wall time)")
        dev_events = load_profiler_dir(args.profile_dir)
    doc = merge_timeline(dumps, device_events=dev_events,
                         device_wall_start=args.wall_start)
    with open(args.out, "w") as f:
        json.dump(doc, f)
    print("wrote %s: %d events (%d device, %d host-phase) — load in "
          "https://ui.perfetto.dev"
          % (args.out, len(doc["traceEvents"]),
             doc["otherData"]["device_events"],
             doc["otherData"]["host_phase_events"]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
