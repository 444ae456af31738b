"""A cell, a mix, a configuration and a per-layer metric are added from
NEW files only: nothing that the benchmark has is edited.

The test copies ``BENCHMARK.json`` and ``perfbench/`` into a temporary
checkout (the program and its native build are linked in), adds

* ``perfbench/configs/apus_redis_r3_copy.json`` — a configuration,
* ``perfbench/traffic/set_c8.json`` — a mix for the general generator,
* ``perfbench/metrics/protocol_steps_per_dispatch.json`` — a per-layer
  metric for an existing reader kind,
* the matching entries of ``BENCHMARK.json`` (entries are added; none
  that is there changes),

runs the new cell, and compares every pre-existing file byte for byte.
"""

import hashlib
import json
import os
import shutil

from _run import ROOT, RESULT_KEYS, run_cell


def digest(root):
    out = {}
    for base, _dirs, files in os.walk(os.path.join(root, "perfbench")):
        if "__pycache__" in base:
            continue
        for f in files:
            p = os.path.join(base, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def test_add_cell_mix_config_metric_from_new_files(tmp_path):
    co = str(tmp_path / "checkout")
    os.makedirs(co)
    shutil.copytree(os.path.join(ROOT, "perfbench"),
                    os.path.join(co, "perfbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), co)
    for d in ("rdma_paxos_tpu", "native"):
        os.symlink(os.path.join(ROOT, d), os.path.join(co, d))
    before = digest(co)

    bench_path = os.path.join(co, "BENCHMARK.json")
    with open(bench_path) as f:
        bench = json.load(f)
    old = json.loads(json.dumps(bench))

    with open(os.path.join(co, "perfbench/configs/apus_redis_r3.json")) as f:
        cfg = json.load(f)
    cfg["name"] = "apus_redis_r3_copy"
    cfg["source"] += " (a second deployment for the test)"
    with open(os.path.join(
            co, "perfbench/configs/apus_redis_r3_copy.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(co, "perfbench/traffic/set_c8.json"), "w") as f:
        json.dump(dict(generator="resp_closed_loop", connections=8,
                       value_bytes=16, warmup_ops=100, grace_s=5.0), f)
    with open(os.path.join(
            co, "perfbench/metrics/protocol_steps_per_dispatch.json"),
            "w") as f:
        json.dump(dict(name="protocol_steps_per_dispatch",
                       reader="probe_ratio", num=["protocol_steps"],
                       den="phase.device_dispatch.count"), f)

    bench["configs"].append(dict(
        name="apus_redis_r3_copy", source=cfg["source"],
        file="perfbench/configs/apus_redis_r3_copy.json", reduced=[],
        why="test"))
    bench["workloads"].append(dict(
        name="redis_set_c8", config="apus_redis_r3_copy",
        traffic="set_c8", chips=1, why="test: 8 connections, 16-byte values"))
    bench["per_layer"].append(dict(
        name="protocol_steps_per_dispatch", unit="steps", better="lower",
        source="program_counter", layer="dispatch (runtime/driver.py)",
        moves="latency_p50_ms", workloads=["redis_set_c8"]))
    with open(bench_path, "w") as f:
        json.dump(bench, f)

    rc, last, out = run_cell("redis_set_c8", trace=1, root=co)
    assert rc == 0, out[-3000:]
    assert set(last) == RESULT_KEYS | {"rehearsal", "breakdown"}
    assert last["correct"] is True
    assert last["metrics"]["protocol_steps_per_dispatch"]["value"] >= 1
    # a metric without a workloads key is due wherever its end-to-end
    # metric is reported: the new cell got the old ones that name no cell
    rc, last, out = run_cell("redis_set_c8", trace=0, root=co)
    assert rc == 0 and set(last["metrics"]) == {"latency_p50_ms", "setup_s"}

    # nothing that was there changed; entries were only added
    after = digest(co)
    assert all(after[k] == v for k, v in before.items())
    assert sorted(set(after) - set(before)) == [
        "perfbench/configs/apus_redis_r3_copy.json",
        "perfbench/metrics/protocol_steps_per_dispatch.json",
        "perfbench/traffic/set_c8.json"]
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        assert bench[key][:len(old[key])] == old[key]
