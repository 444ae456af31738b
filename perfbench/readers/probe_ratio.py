"""sum(num terms) / den term * scale, each term a change over the
window (see ``_terms``). Nothing to read where a term is absent or the
denominator did not move."""

from perfbench.readers._terms import term


def read(spec, view):
    nums = [term(n, view) for n in spec["num"]]
    den = term(spec["den"], view)
    if den is None or den <= 0 or all(n is None for n in nums):
        return None
    return sum(n for n in nums if n is not None) / den * spec.get("scale", 1)
