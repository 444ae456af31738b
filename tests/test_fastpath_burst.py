"""Stable fast path (``elections=False``) + multi-step burst dispatch.

The reference's latency story is a µs-scale busy commit loop on the NIC
(``rc_write_remote_logs`` ``dare_ibv_rc.c:1870-1948``). Here the analogs are
(a) the STABLE protocol step with the election phase statically removed —
one fewer collective per step — dispatched whenever no election timer
fired, and (b) the K-step burst (``lax.scan``) that amortizes host→device
dispatch over many protocol steps. Both must be behavior-identical to the
full step; these tests pin that down, including the failure interactions
(deposition around a burst, partitioned leader inside a burst)."""

import numpy as np
import pytest

import jax

from rdma_paxos_tpu.config import LogConfig
from rdma_paxos_tpu.consensus.state import Role
from rdma_paxos_tpu.runtime.sim import SimCluster
from tests.readback_ref import assert_same, drive

CFG = LogConfig(n_slots=64, slot_bytes=32, window_slots=16, batch_slots=8)


def _drive(c, n_ops=5, extra_steps=2):
    c.step(timeouts=[0])
    for i in range(n_ops):
        c.submit(0, b"op-%04d" % i)
        c.step()
    for _ in range(extra_steps):
        c.step()


def test_stable_step_bit_identical_to_full_step():
    """On iterations with no timeout fired, the stable step must produce
    bit-identical state AND outputs vs the full step (the docstring's
    contract in consensus/step.py)."""
    full = SimCluster(CFG, 3, stable_fast_path=False)
    fast = SimCluster(CFG, 3, stable_fast_path=True)
    _drive(full)
    _drive(fast)
    for k in full.last:
        assert np.array_equal(full.last[k], fast.last[k]), k
    for a, b in zip(jax.tree.leaves(full.state), jax.tree.leaves(fast.state)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_stable_step_compiles_and_steps():
    """Regression: elections=False used to crash with UnboundLocalError
    at trace time (advisor round-2 finding)."""
    c = SimCluster(CFG, 3, stable_fast_path=True)
    c.run_until_elected(0)
    c.submit(0, b"hello")
    res = c.step()          # no timeouts -> stable step dispatched
    assert res["commit"][0] >= 1


def test_stable_step_still_adopts_higher_term():
    """A deposed leader must step down even in stable steps (term adoption
    and window absorption are NOT part of Phase B)."""
    c = SimCluster(CFG, 3, stable_fast_path=False)
    c.run_until_elected(0)
    # partition 0 away; elect 1 at a higher term
    c.partition([[0], [1, 2]])
    c.step(timeouts=[1])
    assert c.last["role"][1] == int(Role.LEADER)
    c.heal()
    # healed step WITHOUT timeouts — force the stable path explicitly
    c._stable_fast_path = True
    res = c.step()
    assert res["role"][0] != int(Role.LEADER)
    assert res["term"][0] == res["term"][1]
    assert res["leader_id"][0] == 1


def test_vote_records_refresh_on_stable_steps_after_heal():
    """The durable vote pair now rides the control gather, so a replica
    partitioned during an election learns peers' votes on the first healed
    step — even a stable one."""
    c = SimCluster(CFG, 3, stable_fast_path=False)
    c.run_until_elected(0)
    c.partition([[2], [0, 1]])
    c.step(timeouts=[1])    # 1 elected at term 2; 2 heard nothing
    assert c.last["role"][1] == int(Role.LEADER)
    rec_before = np.asarray(c.state.vote_rec_term)[2]
    c.heal()
    c._stable_fast_path = True
    c.step()                # stable step: retention via control gather
    rec_after = np.asarray(c.state.vote_rec_term)[2]
    assert rec_after.max() > rec_before.max()


# ---------------------------------------------------------------------------
# burst dispatch
# ---------------------------------------------------------------------------

def test_burst_deep_queue_drain():
    """A deep queue drains through one burst dispatch with every entry
    committed in order."""
    c = SimCluster(CFG, 3)
    c.run_until_elected(0)
    c.step()
    n = 40                              # 5 batches -> K=8 tier
    for i in range(n):
        c.submit(0, b"b%04d" % i)
    res = c.step_burst()
    assert int(res["accepted"][0]) == n
    assert int(res["commit"][0]) >= n   # NOOP + n, minus lazy tail
    c.step()
    for r in range(3):
        assert [p for (_, _, _, p) in c.replayed[r]] == \
            [b"b%04d" % i for i in range(n)]


def test_burst_near_ring_full_sizing_requeues_rest():
    """Sizing must clamp the burst to ring capacity and leave the
    remainder queued — never drop or reorder."""
    c = SimCluster(CFG, 3)
    c.run_until_elected(0)
    c.step()
    n = 120                             # ring holds 63
    for i in range(n):
        c.submit(0, b"r%04d" % i)
    for _ in range(60):
        if not c.pending[0]:
            break
        c.step_burst()
        # let pruning free space (apply echo)
        c.step()
    assert not c.pending[0]
    c.step()
    for r in range(3):
        assert [p for (_, _, _, p) in c.replayed[r]] == \
            [b"r%04d" % i for i in range(n)]


def test_burst_after_leadership_change():
    """A burst issued right after a leadership change (old leader's queue
    still loaded) must not commit via the deposed leader."""
    c = SimCluster(CFG, 3)
    c.run_until_elected(0)
    c.step()
    for i in range(20):
        c.submit(0, b"x%04d" % i)
    # depose 0: elect 1 at a higher term while 0 is partitioned
    c.partition([[0], [1, 2]])
    c.step(timeouts=[1])
    c.heal()
    c.step()                            # 0 steps down, absorbs 1's window
    assert c.last["role"][0] != int(Role.LEADER)
    res = c.step_burst()                # 0's queue nonempty but 0 follower
    # nothing from 0's queue was appended by a non-leader
    assert int(res["accepted"][0]) == 0
    stream = [p for (_, _, _, p) in c.replayed[1]]
    assert b"x0000" not in stream


def test_burst_with_partitioned_leader_no_commit_no_divergence():
    """Leader partitioned right before a burst: it appends locally but
    cannot commit (no quorum); after heal + re-election the divergent
    suffix is truncated and the cluster converges."""
    c = SimCluster(CFG, 3)
    c.run_until_elected(0)
    c.submit(0, b"committed")
    c.step()
    c.step()
    commit0 = int(c.last["commit"][0])
    c.partition([[0], [1, 2]])
    for i in range(10):
        c.submit(0, b"lost%04d" % i)
    res = c.step_burst()                # leader-only burst: appends, no commit
    assert int(res["commit"][0]) == commit0
    assert int(res["end"][0]) > commit0
    # majority side elects a new leader and commits new traffic
    c.step(timeouts=[1])
    assert c.last["role"][1] == int(Role.LEADER)
    c.submit(1, b"won")
    c.step()
    c.heal()
    for _ in range(4):
        c.step()
    # old leader converged onto the new history; its lost suffix is gone
    assert int(c.last["end"][0]) == int(c.last["end"][1])
    stream0 = [p for (_, _, _, p) in c.replayed[0]]
    assert b"won" in stream0
    assert not any(p.startswith(b"lost") for p in stream0)


def test_burst_shortfall_requeues_instead_of_raising():
    """If a burst cannot append everything (ring pressure), the remainder
    must be requeued in order on the pending queue — the poll thread must
    never see an exception."""
    small = LogConfig(n_slots=16, slot_bytes=32, window_slots=8,
                      batch_slots=4)
    c = SimCluster(small, 3)
    c.run_until_elected(0)
    c.step()
    for i in range(30):
        c.submit(0, b"s%02d" % i)
    for _ in range(20):
        if not c.pending[0]:
            break
        c.step_burst()
        c.step()
    c.step()
    assert [p for (_, _, _, p) in c.replayed[0]] == \
        [b"s%02d" % i for i in range(30)]


# entries a dispatch: 5 ride a serial step, 12 the tier K=2, 100 the
# tier K=16 (batch_slots=8)
PACKED_CFG = LogConfig(n_slots=512, slot_bytes=32, window_slots=16,
                       batch_slots=8)


@pytest.mark.parametrize("n,K", [(5, 1), (12, 2), (100, 16)],
                         ids=["step", "burst_k2", "burst_k16"])
def test_packed_row_unpacks_to_fieldwise_readback(n, K):
    """The ONE array a dispatch reads back unpacks to exactly what the
    per-field reads returned: the final step's row of every StepOutput
    field the host rules consume, ``accepted`` summed over the burst,
    and the config view of the post-step state."""
    c = SimCluster(PACKED_CFG, 3)
    seen = drive(c, n, fused=K > 1)
    assert max(k for _, k, _, _ in seen) == K
    for kind, _, res, ref in seen:
        assert_same(res, ref)
    assert c.last["commit"][0] == 1 + 3 * n
