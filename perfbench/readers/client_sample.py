"""A statistic of the generator's sample: the operations whose reply
arrived inside the window, as the client saw them."""

from perfbench.harness.sample import percentile


def read(spec, view):
    sample = view["sample"]
    stat = spec["stat"]
    if stat == "ops_per_s":
        return len(sample.completions) / view["window_s"]
    if not sample.latencies_ms:
        return None
    if stat.startswith("latency_p"):
        return percentile(sample.latencies_ms, float(stat[len("latency_p"):]))
    raise ValueError(f"client_sample: unknown stat {stat!r}")
