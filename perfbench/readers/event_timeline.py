"""What happened round the events a deployment stamps on the monotonic
clock (``deployment.events``: name -> stamp), read beside the
generator's completions:

* ``between``: milliseconds from event ``from`` to event ``to``;
* ``gap_after``: the longest silence between two completions in the
  ``seconds`` after ``event`` (or up to the window's close), in
  milliseconds: how long service paused there;
* ``rate_between``: completions per second from ``from`` to ``to``.

Nothing to read where the deployment stamps no events (another kind, or
a program that cannot run the schedule) or an event did not happen.
"""

from perfbench.harness.sample import longest_gap


def read(spec, view):
    events = getattr(view["deployment"], "events", None)
    if not events:
        return None
    stat = spec["stat"]
    stamps = view["sample"].all_completions
    if stat == "gap_after":
        t = events.get(spec["event"])
        if t is None:
            return None
        gap, _at = longest_gap(
            stamps, t, min(t + spec["seconds"], view["t_close"]))
        return gap * 1e3
    a, b = events.get(spec["from"]), events.get(spec["to"])
    if a is None or b is None or b <= a:
        return None
    if stat == "between":
        return (b - a) * 1e3
    if stat == "rate_between":
        return sum(1 for t in stamps if a <= t < b) / (b - a)
    raise ValueError(f"event_timeline: unknown stat {stat!r}")
