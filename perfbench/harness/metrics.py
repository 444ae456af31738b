"""Turn a run's view into the ``metrics`` object of the result line.

Each metric named in ``BENCHMARK.json`` has a file
``perfbench/metrics/<name>.json`` that names its reader kind and what
it reads; each reader kind is ``perfbench/readers/<kind>.py`` with one
function ``read(spec, view) -> float | None``. A reader that finds
nothing to read returns ``None`` and the metric is left out."""

from __future__ import annotations

from typing import Dict, List

from perfbench.harness import spec as _spec


def read_all(entries: List[dict], view: dict) -> Dict[str, dict]:
    out: Dict[str, dict] = {}
    for m in entries:
        mspec = _spec.Cell.metric_spec(m["name"])
        reader = _spec.component("readers", mspec["reader"])
        value = reader.read(mspec, view)
        if value is None:
            continue
        out[m["name"]] = dict(value=float(value), unit=m["unit"])
    return out
