"""The change of probe counters over the window; several keys combine
by ``max`` or ``sum``. For counts that are usually 0."""

from perfbench.readers._terms import term


def read(spec, view):
    vals = [term(k, view) for k in spec["keys"]]
    vals = [v for v in vals if v is not None]
    if not vals:
        return None
    return max(vals) if spec.get("combine", "sum") == "max" else sum(vals)
