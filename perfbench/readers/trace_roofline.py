"""Share of the memory roofline of the programs matching ``modules``:
the bytes their dispatches had to move (``harness/bytes_model.py``, from
the deployment's log shapes and the operations the window carried) over
the chip's peak bandwidth (``peaks.json``), over their device time."""

from perfbench.harness import bytes_model
from perfbench.readers._terms import term
from perfbench.readers._trace import per_dispatch_us


def read(spec, view):
    us = per_dispatch_us(view, spec["modules"], "modules")
    n = term(view["deployment"].DISPATCH_COUNTER, view)
    peaks = view["cell"].peaks.get(view["ctx"].device_kind)
    if not us or not n or peaks is None:
        return None
    shapes = view["deployment"].shapes()
    moved = bytes_model.min_bytes_per_dispatch(
        term("ops", view) / n, shapes["replicas"], shapes["entry_bytes"])
    return bytes_model.roofline_share_pct(
        moved, us / 1e6, peaks["hbm_bytes_per_s"],
        chips=shapes["chips_holding_state"])
