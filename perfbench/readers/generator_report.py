"""A number the generator reports about itself (its CPU share, its
turnaround): how far the load generator, not the system, set the pace."""


def read(spec, view):
    return view["sample"].report.get(spec["key"])
