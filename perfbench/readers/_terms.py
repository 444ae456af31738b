"""Terms of the probe readers: ``ops`` (acknowledged operations in the
window), ``window_s``, or the name of a counter in the deployment's
probe, whose value is close minus open."""


def term(name, view):
    if name == "ops":
        return float(len(view["sample"].completions))
    if name == "window_s":
        return float(view["window_s"])
    a, b = view["probe_open"].get(name), view["probe_close"].get(name)
    if a is None or b is None:
        return None
    return float(b) - float(a)
