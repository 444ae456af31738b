"""``redis_ycsb_a_c50_g3r3_x4`` at ``--rehearse-cpu`` (``run.py`` forces
four host devices): ``redis_ycsb_a_c50_g3r3``'s three groups behind
``ShardedClusterDriver(..., mesh=(1, 3))``, replica r's ring row of
every group on device r. (``test_rehearse.py`` runs the cell's two
lines with every other cell's.)

* the sound run ends ``correct`` with the placement rule met and said;
* the traced run reports the put's two metrics, the collectives and
  every group in every dispatch;
* ``group_replay_dropped`` ends ``correct`` false, under the one
  (app, group) pair and what follows from it;
* a configuration whose ``mesh`` does not fit its replicas, groups or
  chips is refused before anything starts.

    JAX_PLATFORMS=cpu python -m pytest perfbench/tests/test_cluster_mesh.py -q
"""

import json
import os

import pytest

from _run import RESULT_KEYS, ROOT, run_cell

from perfbench.deployments import interposed_app_cluster_mesh as kind

CELL = "redis_ycsb_a_c50_g3r3_x4"


def config() -> dict:
    with open(os.path.join(ROOT, "perfbench", "configs",
                           "apus_redis_g3r3_x4_ycsb_a.json")) as f:
        return json.load(f)


def checks(out: str) -> dict:
    return {c["name"]: c for c in (
        json.loads(ln[len("[check] "):]) for ln in out.splitlines()
        if ln.startswith("[check] "))}


def test_configuration_is_g3s_but_for_the_mapping():
    cfg = config()
    with open(os.path.join(ROOT, "perfbench", "configs",
                           "apus_redis_g3r3_ycsb_a.json")) as f:
        g3 = json.load(f)
    assert cfg["guarantees"] == g3["guarantees"]
    assert cfg["reduced"] == [] and cfg["chips"] == 4
    assert {k: v for k, v in cfg["assumed"].items()
            if k != "one_process"} == g3["assumed"]
    for key in ("replicas", "groups", "rehearsal_geometry",
                "driver_options", "prewarm_burst_tiers", "timers", "app"):
        assert cfg[key] == g3[key], key
    assert {k: v for k, v in cfg["geometry"].items() if k != "note"} == {
        k: v for k, v in g3["geometry"].items() if k != "note"}
    assert kind.mesh_of(cfg) == (1, 3)


def test_sound_run_meets_the_placement_rule():
    rc, last, out = run_cell(CELL, seed=2147483659)     # past 2**31
    assert rc == 0, out[-3000:]
    assert set(last) == RESULT_KEYS | {"rehearsal", "compared"}
    assert last["correct"] is True and last["failed"] == 0
    assert set(last["metrics"]) == {"ops_per_s", "latency_p50_ms",
                                    "latency_p95_ms", "setup_s"}
    assert ("each of 3 chips holds one replica's row of 3 of 3 groups; "
            "chips without a ring row: ['TFRT_CPU_3']") in out
    assert "g0:TFRT_CPU_0,TFRT_CPU_1,TFRT_CPU_2" in out
    cs = checks(out)
    assert all(c["ok"] for c in cs.values())
    assert {f"inadmissible_fields_r{r}_g{g}" for r in range(3)
            for g in range(3)} <= set(cs)
    assert all(cs[f"records_r{r}"]["got"] == 1000 for r in range(3))
    assert "term=((0, " in out and "(1, " in out and "(2, " in out


def test_traced_run_reports_the_put_and_the_groups():
    rc, last, out = run_cell(CELL, trace=1)
    assert rc == 0, out[-3000:]
    assert last["correct"] is True
    m = {k: v["value"] for k, v in last["metrics"].items()}
    # one device_put a dispatch and one a fetch; the fetch may skip a
    # dispatch that committed nothing new
    assert 1.5 <= m["input_put_calls_per_dispatch"] <= 2.1
    assert m["input_put_bytes_per_dispatch"] > 0
    assert m["groups_per_dispatch"] > 2.5
    assert m["cfg_rescans_per_dispatch"] == 0.0
    assert 100.0 / 3 <= m["hot_group_ops_share"] <= 60.0
    for name in ("step_device_us", "program_call_us", "input_transfer_us",
                 "fetch_enqueue_us", "fetch_read_us", "quorum_wait_us",
                 "device_idle_share", "fetch_rows_per_fetch",
                 "replay_us_per_follower"):
        assert m[name] > 0, name


def test_group_replay_dropped_is_caught():
    rc, last, out = run_cell(CELL, fault="group_replay_dropped")
    assert rc == 0, out[-3000:]
    assert last["correct"] is False, out[-3000:]
    bad = {n for n, c in checks(out).items() if not c["ok"]}
    assert "inadmissible_fields_r1_g2" in bad
    assert bad <= {"inadmissible_fields_r1_g2", "records_r1",
                   "records_apps_differ_on"}, bad


@pytest.mark.parametrize("change", [
    dict(mapping=dict(kind="group_rows_per_chip", mesh=[1, 2])),
    dict(mapping=dict(kind="group_rows_per_chip", mesh=[2, 3])),
    dict(mapping=dict(kind="group_rows_per_chip", mesh=[1, 3]), chips=1),
    dict(mapping=dict(kind="group_rows_per_chip", mesh=(1, 3.0))),
    dict(mapping=dict(kind="replica_per_chip", mesh=[1, 3])),
    dict(mapping=dict(kind="group_rows_per_chip")),
], ids=["two_of_three_replicas", "groups_not_a_multiple", "too_few_chips",
        "no_list_of_ints", "another_kind", "no_mesh"])
def test_mesh_that_does_not_fit_is_refused_before_anything_starts(change):
    """``ctx`` is None: nothing of the run is touched on the way."""
    with pytest.raises(SystemExit) as exc:
        kind.build(dict(config(), **change), None)
    assert "want kind 'group_rows_per_chip' and mesh" in str(exc.value)
