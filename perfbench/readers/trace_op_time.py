"""Device time of the operations whose name matches ``ops`` (the
collectives, say), per dispatch, in microseconds, median over chips."""

from perfbench.readers._trace import per_dispatch_us


def read(spec, view):
    return per_dispatch_us(view, spec["ops"], "events")
