"""Device time of the operations compiled under a ``jax.named_scope``
whose path matches ``scope`` (a regular expression), per dispatch, in
microseconds: the median over the chips that ran any.

The scope of an operation is the ``tf_op`` stat of its event METADATA in
the ``XLA Ops`` line of a ``/device:TPU:<n>`` plane (the HLO ``op_name``:
``jit(burst)/vmap(append)/scatter``). ``jax.profiler.ProfileData`` shows
an event's own stats only, so this reader walks the ``.xplane.pb`` wire
format itself (``tsl/profiler/protobuf/xplane.proto``), and only the
fields it needs. Operations the compiler inserted (layout copies) carry
no ``tf_op`` and fall under no scope; a program compiled without the
scopes, or a capture with no TPU plane, gives nothing to read.
"""

import functools
import os
import re
import statistics

from perfbench.harness import trace
from perfbench.readers._terms import term

SCOPE_STAT = "tf_op"
# field numbers of xplane.proto
PLANE_NAME, PLANE_LINES, PLANE_EVENT_MD, PLANE_STAT_MD = 2, 3, 4, 5
LINE_NAME, LINE_TIMESTAMP_NS, LINE_EVENTS = 2, 3, 4
EVENT_MD_ID, EVENT_OFFSET_PS, EVENT_DURATION_PS = 1, 2, 3
MD_NAME, MD_STATS = 2, 5
STAT_MD_ID, STAT_STR, STAT_REF = 1, 5, 7


def varint(buf, i):
    val = shift = 0
    while True:
        b = buf[i]
        i += 1
        val |= (b & 0x7F) << shift
        if b < 0x80:
            return val, i
        shift += 7


def fields(buf):
    """(field number, value) of each field of one protobuf message:
    an int for a varint, a memoryview for a length-delimited field,
    None for a fixed-width one (none is read here)."""
    i, n = 0, len(buf)
    while i < n:
        key, i = varint(buf, i)
        wire = key & 7
        if wire == 0:
            val, i = varint(buf, i)
        elif wire == 2:
            size, i = varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif wire == 1:
            val, i = None, i + 8
        elif wire == 5:
            val, i = None, i + 4
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield key >> 3, val


def text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def map_entries(plane_fields, number):
    """{key: value bytes} of a ``map<int64, Message>`` field."""
    out = {}
    for no, val in plane_fields:
        if no == number:
            entry = dict(fields(val))
            out[entry.get(1, 0)] = entry.get(2, b"")
    return out


def line_events(line):
    """-> (name, [(metadata id, start ps, duration ps)]) of one XLine."""
    name, t0_ps, events = "", 0, []
    for no, val in fields(line):
        if no == LINE_NAME:
            name = text(val)
        elif no == LINE_TIMESTAMP_NS:
            t0_ps = val * 1000
        elif no == LINE_EVENTS:
            ev = dict(fields(val))
            events.append((ev.get(EVENT_MD_ID, 0),
                           ev.get(EVENT_OFFSET_PS, 0),
                           ev.get(EVENT_DURATION_PS, 0)))
    return name, [(m, t0_ps + off, dur) for m, off, dur in events]


@functools.lru_cache(maxsize=2)
def scoped_seconds(path: str):
    """{device plane: {scope path: seconds inside the traced window}}"""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    planes = [list(fields(val)) for no, val in fields(space) if no == 1]
    marks, devices = {}, {}
    for plane in planes:
        name = next((text(v) for no, v in plane if no == PLANE_NAME), "")
        metadata = {k: list(fields(md)) for k, md in
                    map_entries(plane, PLANE_EVENT_MD).items()}
        names = {k: next((text(v) for no, v in md if no == MD_NAME), "")
                 for k, md in metadata.items()}
        lines = [line_events(val) for no, val in plane
                 if no == PLANE_LINES] if names else []
        if not name.startswith("/device:"):
            anchors = {k: n for k, n in names.items()
                       if n in (trace.OPEN, trace.CLOSE)}
            for _line, events in lines if anchors else []:
                for md, start, _dur in events:
                    if md in anchors:
                        marks.setdefault(anchors[md], start)
        if not trace.DEVICE_PLANE.match(name):
            continue
        stat_names = {k: next((text(v) for no, v in fields(md)
                               if no == MD_NAME), "")
                      for k, md in
                      map_entries(plane, PLANE_STAT_MD).items()}
        scope_of = {}
        for k, md in metadata.items():
            # a while or a conditional spans its body's operations
            if trace.short_op(names[k])[1] in trace.CONTAINERS:
                continue
            for no, val in md:
                if no != MD_STATS:
                    continue
                st = dict(fields(val))
                if stat_names.get(st.get(STAT_MD_ID)) == SCOPE_STAT:
                    scope_of[k] = (
                        text(st[STAT_STR]) if STAT_STR in st
                        else stat_names.get(st.get(STAT_REF), ""))
        for line_name, events in lines:
            if line_name == trace.OPS_LINE:
                devices[name] = (scope_of, events)
    if trace.OPEN not in marks or trace.CLOSE not in marks:
        return {}
    t_open, t_close = marks[trace.OPEN], marks[trace.CLOSE]
    out = {}
    for name, (scope_of, events) in devices.items():
        total = {}
        for md, start, dur in events:
            scope = scope_of.get(md)
            ov = min(start + dur, t_close) - max(start, t_open)
            if scope and ov > 0:
                total[scope] = total.get(scope, 0.0) + ov / 1e12
        out[name] = total
    return out


def read(spec, view):
    if view["trace"] is None:
        return None
    n = term(view["deployment"].DISPATCH_COUNTER, view)
    try:
        path = trace.Capture(os.path.join(view["ctx"].workdir,
                                          "prof")).xplane_path()
    except RuntimeError:
        return None
    rx = re.compile(spec["scope"])
    vals = [sum(s for scope, s in by_scope.items() if rx.search(scope))
            for by_scope in scoped_seconds(path).values()]
    vals = [v / n * 1e6 for v in vals if v > 0] if n else []
    return statistics.median(vals) if vals else None
