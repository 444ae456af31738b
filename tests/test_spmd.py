"""shard_map path: the identical protocol program over a real 8-device mesh
(virtual CPU devices here; one replica per TPU chip in production). This is
the compilation/sharding contract the driver's dryrun validates."""

import jax
import numpy as np
import pytest

from rdma_paxos_tpu.config import LogConfig
from rdma_paxos_tpu.consensus.state import Role
from rdma_paxos_tpu.runtime.sim import SimCluster
from tests.readback_ref import assert_same, drive

CFG = LogConfig(n_slots=64, slot_bytes=32, window_slots=16, batch_slots=8)

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 (virtual) devices")


def test_spmd_replication_8_replicas():
    c = SimCluster(CFG, 8, mode="spmd")
    c.run_until_elected(0)
    c.submit(0, b"spmd!")
    res = c.step()
    assert res["commit"][0] == 2
    res = c.step()
    assert list(res["commit"]) == [2] * 8
    for r in range(8):
        assert [p for (_, _, _, p) in c.replayed[r]] == [b"spmd!"]


@pytest.mark.parametrize("mode", ["sim", "spmd"])
def test_psum_fanout_matches_gather(mode):
    """The O(W) psum window broadcast must be observably identical to the
    O(R·W) gather-select fan-out under full connectivity (the only regime
    it is specified for): same commits, same replayed bytes, same log.
    Parametrized over both execution modes because the collective
    LOWERING differs only under ``shard_map`` (a real masked all-reduce
    vs an all-gather + select); the vmap simulation lowers both to data
    movement on one device."""
    runs = {}
    for fo in ("gather", "psum"):
        c = SimCluster(CFG, 5, mode=mode, fanout=fo)
        c.run_until_elected(0)
        for i in range(6):
            c.submit(0, b"op-%d" % i)
            c.step()
        # leadership churn under full connectivity: new leader takes over
        c.step(timeouts=[2])
        c.submit(2, b"after-churn")
        for _ in range(3):
            res = c.step()
        runs[fo] = (res, c.replayed, np.asarray(c.state.log.buf))
    rg, replg, bufg = runs["gather"]
    rp, replp, bufp = runs["psum"]
    for k in ("term", "role", "commit", "end", "head"):
        assert list(rg[k]) == list(rp[k]), k
    assert replg == replp
    assert (bufg == bufp).all()


def test_spmd_group3_with_learners():
    """Mesh bigger than the voting group: replicas outside the membership
    bitmask are learners — they absorb the log but neither vote nor count
    toward quorum (the joiner state of the reference before its CONFIG
    entry commits, dare_ibv_ud.c:972-1068)."""
    c = SimCluster(CFG, 8, group_size=3, mode="spmd")
    c.run_until_elected(1)
    c.submit(1, b"learn")
    c.step()
    res = c.step()
    # everyone (members + learners) converges on the log...
    assert list(res["end"]) == [2] * 8
    # ...and commit required only the 3-member quorum
    assert res["commit"][1] == 2


def test_spmd_failover():
    c = SimCluster(CFG, 8, mode="spmd")
    c.run_until_elected(0)
    c.submit(0, b"pre")
    c.step()
    c.step()
    c.partition([[0], list(range(1, 8))])
    res = c.step(timeouts=[3])
    assert res["role"][3] == int(Role.LEADER)
    c.submit(3, b"post")
    res = c.step()
    assert res["commit"][3] == 4


@pytest.mark.parametrize("n,K", [(5, 1), (12, 2), (100, 16)],
                         ids=["step", "burst_k2", "burst_k16"])
def test_spmd_packed_row_unpacks_to_fieldwise_readback(n, K):
    """One replica per device: the packed row is assembled per device
    and read back as one (sharded) array — same values as the
    per-field reads, the failure detector's ``peer_acked`` row and the
    config view included."""
    cfg = LogConfig(n_slots=512, slot_bytes=32, window_slots=16,
                    batch_slots=8)
    c = SimCluster(cfg, 3, mode="spmd", fanout="psum")
    seen = drive(c, n, fused=K > 1)
    assert max(k for _, k, _, _ in seen) == K
    for _, _, res, ref in seen:
        assert_same(res, ref)
    assert c.last["commit"][0] == 1 + 3 * n
