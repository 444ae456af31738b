"""``redis_ycsb_a_c50_g3r3`` at ``--rehearse-cpu``: three groups on
three replicas behind ``ShardedClusterDriver``, the sound run and the
four runs that show its check can come out false. (``test_rehearse.py``
runs the cell's two lines with every other cell's.)

* ``interposed_app_ycsb``'s three: replica 1's app loses every fourth
  replayed write / stores every value with a byte changed (faults of
  the system, on both groups it follows); every tenth acknowledged read
  handed to the reference one version old (a control of the check);
* ``group_replay_dropped``: replica 1's app misses every replayed
  write of group 2 and none of group 0: the fault shows under that
  (app, group) pair and under no other.

The cell was added from new files only (PR 38): the last test holds
every file the benchmark had at the parent commit to its bytes there.

    JAX_PLATFORMS=cpu python -m pytest perfbench/tests/test_cluster.py -q
"""

import json
import os
import subprocess

import pytest

from _run import RESULT_KEYS, ROOT, bench, run_cell

CELL = "redis_ycsb_a_c50_g3r3"
PARENT = "3f87f57e1d25c1f00fd5c2ab2b21d5d6704e3f85"
NEW_FILES = {
    "perfbench/README_cluster.md",
    "perfbench/configs/apus_redis_g3r3_ycsb_a.json",
    "perfbench/deployments/interposed_app_cluster.py",
    "perfbench/generators/resp_ycsb_cluster.py",
    "perfbench/metrics/groups_per_dispatch.json",
    "perfbench/metrics/hot_group_ops_share.json",
    "perfbench/reference/ycsb_register_cluster.py",
    "perfbench/tests/test_cluster.py",
    "perfbench/traffic/ycsb_a_c50_cluster.json",
}


def checks(out: str) -> dict:
    return {c["name"]: c for c in (
        json.loads(ln[len("[check] "):]) for ln in out.splitlines()
        if ln.startswith("[check] "))}


def test_sound_run_asks_every_app_for_every_group():
    rc, last, out = run_cell(CELL, seed=2147483659)     # past 2**31
    assert rc == 0, out[-3000:]
    assert set(last) == RESULT_KEYS | {"rehearsal", "compared"}
    assert last["correct"] is True and last["failed"] == 0
    assert set(last["metrics"]) == {"ops_per_s", "latency_p50_ms",
                                    "latency_p95_ms", "setup_s"}
    cs = checks(out)
    assert all(c["ok"] for c in cs.values())
    assert {f"inadmissible_fields_r{r}_g{g}" for r in range(3)
            for g in range(3)} <= set(cs)
    assert all(cs[f"records_r{r}"]["got"] == 1000 for r in range(3))
    assert cs["records_in_groups"]["got"] == 1000
    assert cs["group_completions_off"]["got"] == 0
    assert int(cs["stale_read_fields"]["what"].split()[1]) > 100
    # group g on replica g, and every number beside its limit
    assert "term=((0, " in out and "(1, " in out and "(2, " in out
    assert "perfbench: compared group_completions_off: got 0, limit 0" in out


def test_traced_run_reports_the_groups():
    rc, last, out = run_cell(CELL, trace=1)
    assert rc == 0, out[-3000:]
    assert last["correct"] is True
    m = {k: v["value"] for k, v in last["metrics"].items()}
    assert 1.0 <= m["groups_per_dispatch"] <= 3.0
    assert 100.0 / 3 <= m["hot_group_ops_share"] <= 60.0
    assert abs(m["entries_per_op"] - 1.0) < 0.05
    # two followers' apps answer every replayed operation
    assert 0.5 < m["replay_applies_per_dispatch"] / (
        2 * m["ops_per_dispatch"]) < 1.5
    assert m["replay_us_per_follower"] > 0
    assert m["cfg_rescans_per_dispatch"] >= 0
    for name in ("store_append_us_per_op", "replay_send_us_per_op",
                 "replay_drain_us", "intake_queue_wait_us_per_op",
                 "intake_to_ack_us_per_op", "replay_fetch_phase_us",
                 "replay_decode_us_per_op", "input_transfer_us",
                 "post_readback_us", "readback_arrays_per_dispatch",
                 "step_device_us", "post_step_rules_us", "admin_gate_us"):
        assert m[name] > 0, name


@pytest.mark.parametrize("fault,caught_by", [
    ("follower_drops_applies", "inadmissible_fields_r1_g"),
    ("follower_alters_values", "inadmissible_fields_r1_g"),
    ("stale_read_control", "stale_read_fields"),
    ("group_replay_dropped", "inadmissible_fields_r1_g2"),
])
def test_fault_is_caught(fault, caught_by):
    rc, last, out = run_cell(CELL, fault=fault)
    assert rc == 0, out[-3000:]
    assert last["correct"] is False, out[-3000:]
    bad = {n for n, c in checks(out).items() if not c["ok"]}
    assert any(n.startswith(caught_by) for n in bad), bad
    if fault == "group_replay_dropped":
        # that app, that group, and what follows from it; no other
        # (the load's inserts of that group are replayed writes too)
        assert bad <= {"inadmissible_fields_r1_g2", "records_r1",
                       "records_apps_differ_on"}, bad
    if fault == "stale_read_control":
        assert bad == {"stale_read_fields"}, bad    # the system ran sound


def test_election_faults_are_refused():
    rc, last, out = run_cell(CELL, fault="election_under_warm_up")
    assert rc != 0 and last is None
    assert "is not for a deployment of 3 groups" in out


def test_the_benchmark_that_was_there_is_there_byte_for_byte():
    def git(*args):
        return subprocess.run(["git", "-C", ROOT, *args],
                              capture_output=True)
    if git("cat-file", "-e", PARENT + "^{commit}").returncode != 0:
        pytest.skip("no git history with the parent commit here")
    listed = git("ls-tree", "-r", "--name-only", PARENT, "perfbench")
    for path in listed.stdout.decode().split():
        with open(os.path.join(ROOT, path), "rb") as f:
            assert f.read() == git("show", f"{PARENT}:{path}").stdout, path
    now = {os.path.relpath(os.path.join(base, f), ROOT)
           for base, _d, files in os.walk(os.path.join(ROOT, "perfbench"))
           if "__pycache__" not in base for f in files}
    assert now - set(listed.stdout.decode().split()) == NEW_FILES
    old = json.loads(git("show", f"{PARENT}:BENCHMARK.json").stdout)
    new = bench()
    for key in ("command", "paths", "run_seconds"):
        assert new[key] == old[key]
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        for was, is_ in zip(old[key], new[key]):
            lists = is_.get("workloads", [])
            if CELL in lists:       # appended, last, and nothing else
                assert lists[-1] == CELL
                is_ = dict(is_, workloads=lists[:-1])
            assert was == is_, was["name"]
