"""The field-by-field readback the packed row replaced, kept as the
reference its tests compare with: one array per ``StepOutput`` field
(the final step's row of a fused dispatch, ``accepted`` summed over
it) and the config view read off the device state."""

import numpy as np

from rdma_paxos_tpu.consensus.step import CONFIG_VIEW_KEYS

FIELDS = ("term", "role", "leader_id", "voted_term", "voted_for",
          "head", "apply", "commit", "end", "hb_seen", "became_leader",
          "acked", "accepted", "peer_acked", "leadership_verified",
          "rebase_delta", "burst_hint")


def fieldwise(c, ticket):
    """``ticket``'s reference ``res``; call after ``finish`` and before
    the next dispatch (the config view is the post-step state's)."""
    out = ticket.out
    ref = {}
    for k in FIELDS:
        v = np.asarray(getattr(out, k))
        if ticket.kind != "step":
            v = (v.sum(axis=0, dtype=v.dtype) if k == "accepted"
                 else v[-1])
        ref[k] = v
    for k in CONFIG_VIEW_KEYS:
        ref[k] = np.asarray(getattr(c.state, k))
    return ref


def assert_same(res, ref):
    for k, v in ref.items():
        assert res[k].dtype == v.dtype, (k, res[k].dtype, v.dtype)
        assert np.array_equal(res[k], v), (k, res[k], v)


def drive(c, n, fused=True, rounds=3):
    """Elect replica 0 through the serial step, then ``rounds`` times
    ``n`` entries through one dispatch (a burst or scan if ``fused``,
    else a serial step) with an idle serial step after each. Returns ``[(kind, K, res, ref)]`` per dispatch; ``ref`` is None for
    a scan ticket, whose program returns no field-by-field outputs."""
    seen = []

    def finish(ticket):
        res = c.finish(ticket)
        ref = None if ticket.kind == "scan" else fieldwise(c, ticket)
        seen.append((ticket.kind, ticket.K, res, ref))

    finish(c.begin_step(timeouts=[0]))
    finish(c.begin_step())
    for i in range(rounds):
        for j in range(n):
            c.submit(0, b"r%d-%03d" % (i, j))
        finish(c.begin_burst() if fused else c.begin_step())
        finish(c.begin_step())
    return seen
