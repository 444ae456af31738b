"""Shared by the trace readers: which devices to read, dispatches in
the traced window."""

import re
import statistics

from perfbench.readers._terms import term


def devices(view):
    tr = view["trace"]
    if tr is None:
        return {}
    return {k: d for k, d in tr["devices"].items() if d["busy_s"] > 0}


def matching_seconds(device, pattern, field="events"):
    rx = re.compile(pattern)
    return sum(b - a for a, b, name in device[field] if rx.search(name))


def per_dispatch_us(view, pattern, field):
    """Seconds of ``field`` entries matching ``pattern`` per dispatch, in
    microseconds: the median over the chips that ran any."""
    devs = devices(view)
    n = term(view["deployment"].DISPATCH_COUNTER, view)
    if not devs or not n:
        return None
    vals = [matching_seconds(d, pattern, field) / n * 1e6
            for d in devs.values()]
    vals = [v for v in vals if v > 0]
    if not vals:
        return None
    return statistics.median(vals)
