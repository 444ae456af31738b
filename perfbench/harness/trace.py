"""Profiler capture and the reduction from an ``.xplane.pb`` to numbers.

The capture runs ``jax.profiler`` without the Python tracer (it would
slow the host path under test and swell the file) and writes two host
annotations, ``perfbench_open`` and ``perfbench_close``, whose
``time.monotonic()`` stamps tie the trace's clock to the host's: the
program's own phase events and the generator's stamps are on that
clock. Everything between the two anchors is the traced window.

The reduction reads the file with ``jax.profiler.ProfileData`` only.
Device operations are the events of each ``/device:TPU:<n>`` plane's
``XLA Ops`` line, named ``<module>/<op>`` by the ``XLA Modules`` event
that contains them. A capture without such a plane is an error, except
in the CPU rehearsal (``rehearsal=True``), where the host plane's events
that carry an ``hlo_op`` stat stand in as one pseudo device so that the
same code runs in the sandbox's tests: never under ``"platform": "tpu"``.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
import time
from typing import Dict, List, Sequence, Tuple

OPEN, CLOSE = "perfbench_open", "perfbench_close"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
MIN_GAP_S = 0.0005          # idle gaps shorter than this are not named


class Capture:
    """One bounded ``jax.profiler`` trace with the two anchors."""

    def __init__(self, log_dir: str):
        self.log_dir = log_dir
        self.anchors: Dict[str, float] = {}

    def _mark(self, name: str) -> None:
        import jax
        self.anchors[name] = time.monotonic()
        with jax.profiler.TraceAnnotation(name):
            pass

    def start(self) -> "Capture":
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        os.makedirs(self.log_dir, exist_ok=True)
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)
        self._mark(OPEN)
        return self

    def close_window(self) -> None:
        """The window's end; the trace itself may go on (stopping it
        stalls the process while the file is written)."""
        self._mark(CLOSE)

    def stop(self) -> "Capture":
        import jax
        if CLOSE not in self.anchors:
            self.close_window()
        jax.profiler.stop_trace()
        return self

    def xplane_path(self) -> str:
        found = sorted(glob.glob(os.path.join(
            self.log_dir, "**", "*.xplane.pb"), recursive=True))
        if not found:
            raise RuntimeError(f"no .xplane.pb under {self.log_dir}")
        return found[-1]


def _union(intervals: List[Tuple[float, float]]
           ) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _stat(event, key: str):
    for k, v in event.stats:
        if k == key:
            return v
    return None


def _find_anchors(planes) -> Dict[str, float]:
    found: Dict[str, float] = {}
    for pl in planes:
        if pl.name.startswith("/device:"):
            continue
        for ln in pl.lines:
            for e in ln.events:
                if e.name in (OPEN, CLOSE) and e.name not in found:
                    found[e.name] = float(e.start_ns)
    return found


CONTAINERS = ("while", "conditional", "call")   # their time is their body's


def short_op(text: str) -> Tuple[str, str]:
    """``%copy.2 = s32[3,16,8]{...} copy(...)`` -> (``copy.2 s32[3,16,8]``,
    ``copy``); a name that is not HLO text is kept as it is."""
    name, sep, rest = text.partition(" = ")
    if not sep:
        return text[:120], ""
    if rest.startswith("("):
        depth, i = 0, 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        shape, rest = "(tuple)", rest[i + 1:]
    else:
        m = re.match(r"[a-z0-9]+\[[^\]]*\]", rest)
        shape = m.group(0) if m else ""
    m = re.search(r" ([\w\-]+)\(", rest)
    return (f"{name.lstrip('%')} {shape}".strip(),
            m.group(1) if m else "")


def _device_events(planes, rehearsal: bool = False) -> Dict[str, dict]:
    """-> {device: {"events": [(start_ns, end_ns, "<module>/<op>")],
    "modules": [(start_ns, end_ns, module)]}}; container operations
    (a ``while`` spans its body's operations) are left out of events.
    Raises when no TPU plane ran anything, unless ``rehearsal``."""
    devices: Dict[str, dict] = {}
    for pl in planes:
        if not DEVICE_PLANE.match(pl.name):
            continue
        mods: List[Tuple[float, float, str]] = []
        ops: List[Tuple[float, float, str]] = []
        for ln in pl.lines:
            if ln.name == MODULES_LINE:
                mods = sorted((float(e.start_ns),
                               float(e.start_ns + e.duration_ns),
                               re.sub(r"\(\d+\)$", "", e.name))
                              for e in ln.events)
            elif ln.name == OPS_LINE:
                ops = [(float(e.start_ns),
                        float(e.start_ns + e.duration_ns), e.name)
                       for e in ln.events]
        starts = [m[0] for m in mods]
        shorts: Dict[str, Tuple[str, str]] = {}
        named = []
        for a, b, text in ops:
            if text not in shorts:
                shorts[text] = short_op(text)
            name, opcode = shorts[text]
            if opcode in CONTAINERS:
                continue
            i = bisect.bisect_right(starts, a) - 1
            mod = mods[i][2] if i >= 0 and a < mods[i][1] else "?"
            named.append((a, b, f"{mod}/{name}"))
        if named:
            devices[pl.name] = dict(events=named, modules=mods)
    if devices:
        return devices
    if not rehearsal:
        raise RuntimeError(
            "the capture has no /device:TPU plane with operations "
            f"(planes: {[pl.name for pl in planes]}): host events are "
            "never counted as device time outside --rehearse-cpu")
    # no chip: XLA:CPU's thunk events on the host plane, one pseudo device
    host: List[Tuple[float, float, str]] = []
    mods = []
    for pl in planes:
        if pl.name.startswith("/device:"):
            continue
        for ln in pl.lines:
            for e in ln.events:
                if e.duration_ns <= 0:
                    continue
                op = _stat(e, "hlo_op")
                if op is None or op.split(".")[0] in CONTAINERS:
                    continue
                a, b = float(e.start_ns), float(e.start_ns + e.duration_ns)
                mod = _stat(e, "hlo_module") or "?"
                host.append((a, b, f"{mod}/{op}"))
                mods.append((a, b, mod))
    return ({"host-as-device": dict(events=host, modules=mods)}
            if host else {})


def _load(path: str):
    """``ProfileData`` of an ``.xplane.pb``, or of a gzipped one (the
    recorded trace kept with the tests)."""
    import jax
    if path.endswith(".gz"):
        import gzip
        with gzip.open(path, "rb") as f:
            return jax.profiler.ProfileData.from_serialized_xspace(f.read())
    return jax.profiler.ProfileData.from_file(path)


def reduce_trace(path: str, anchors: Dict[str, float],
                 phase_events: Sequence[Tuple[str, float, float]] = (),
                 top: int = 10, rehearsal: bool = False) -> dict:
    """Reduce one capture. ``anchors`` are the monotonic stamps of the
    two annotations; ``phase_events`` are ``(name, t0, t1)`` host spans
    on the monotonic clock, used to name the idle gaps; ``rehearsal``
    admits the CPU stand-in for a device (see the module's text).

    Returns ``window_s``; per device its clipped ``events`` and
    ``modules`` ``[(t0_s, t1_s, name)]`` (seconds from the window's
    start), ``busy_s`` and ``gaps``; ``busy_s`` averaged over the devices that ran anything;
    ``device_ops`` and ``idle_gaps`` for the result line's breakdown."""
    planes = list(_load(path).planes)
    marks = _find_anchors(planes)
    if OPEN not in marks or CLOSE not in marks:
        raise RuntimeError(f"trace {path} lacks the {OPEN}/{CLOSE} anchors")
    t_open, t_close = marks[OPEN], marks[CLOSE]
    window_s = (t_close - t_open) / 1e9
    # monotonic seconds -> seconds from the window's start
    mono0 = anchors[OPEN]
    per_device = {}
    def clip(evs):
        return [((max(a, t_open) - t_open) / 1e9,
                 (min(b, t_close) - t_open) / 1e9, name)
                for a, b, name in evs if b > t_open and a < t_close]
    for dev, found in sorted(_device_events(planes, rehearsal).items()):
        clipped = clip(found["events"])
        busy = _union([(a, b) for a, b, _ in clipped])
        gaps, prev = [], 0.0
        for a, b in busy + [(window_s, window_s)]:
            if a - prev >= MIN_GAP_S:
                gaps.append((prev, a))
            prev = max(prev, b)
        per_device[dev] = dict(events=clipped,
                               modules=clip(found["modules"]),
                               busy_s=sum(b - a for a, b in busy),
                               gaps=gaps)
    used = [d for d in per_device.values() if d["busy_s"] > 0]
    by_name: Dict[str, float] = {}
    for d in used:
        for a, b, name in d["events"]:
            by_name[name] = by_name.get(name, 0.0) + (b - a)
    n_used = max(len(used), 1)
    device_ops = sorted(((k, v / n_used) for k, v in by_name.items()),
                        key=lambda kv: -kv[1])[:top]
    by_module: Dict[str, float] = {}
    for d in used:
        for a, b, name in d["modules"]:
            by_module[name] = by_module.get(name, 0.0) + (b - a) / n_used
    return dict(
        window_s=window_s, devices=per_device, module_seconds=by_module,
        busy_s=(sum(d["busy_s"] for d in used) / n_used),
        device_ops=[[k, v] for k, v in device_ops],
        idle_gaps=name_gaps(used[0]["gaps"] if used else [],
                            [(n, a - mono0, b - mono0)
                             for n, a, b in phase_events], top))


def name_gaps(gaps: Sequence[Tuple[float, float]],
              phases: Sequence[Tuple[str, float, float]],
              top: int = 10) -> List[List]:
    """Name each idle gap of the first device by the host phase that
    covered most of it (``none`` where no phase did), and total the
    gap time by that name."""
    phases = sorted(phases, key=lambda p: p[1])
    starts = [p[1] for p in phases]
    longest = max((p[2] - p[1] for p in phases), default=0.0)
    total: Dict[str, float] = {}
    for g0, g1 in gaps:
        cover: Dict[str, float] = {}
        i = bisect.bisect_left(starts, g0 - longest)
        while i < len(phases) and phases[i][1] < g1:
            n, a, b = phases[i]
            ov = min(b, g1) - max(a, g0)
            if ov > 0:
                cover[n] = cover.get(n, 0.0) + ov
            i += 1
        name = max(cover, key=cover.get) if cover else "none"
        if cover and cover[name] < 0.5 * (g1 - g0):
            name = "none"
        total[name] = total.get(name, 0.0) + (g1 - g0)
    return [[k, v] for k, v in
            sorted(total.items(), key=lambda kv: -kv[1])[:top]]


def dump_structure(path: str, per_line: int = 3) -> None:
    """Print planes, lines and the first events of each with their
    stats: look at a trace by hand before writing code against it."""
    for pl in _load(path).planes:
        print("PLANE", pl.name)
        for ln in pl.lines:
            evs = list(ln.events)
            print(f"  LINE {ln.name!r} events={len(evs)}")
            for e in evs[:per_line]:
                print(f"    {e.name!r} start_ns={e.start_ns} "
                      f"dur_ns={e.duration_ns} stats={dict(e.stats)}")
