"""Device time of the programs whose module name matches ``modules``,
per dispatch, in microseconds: the ``XLA Modules`` events of the
device trace (median over the chips that ran them)."""

from perfbench.readers._trace import per_dispatch_us


def read(spec, view):
    return per_dispatch_us(view, spec["modules"], "modules")
