"""chip_smoke.py's contract where no chip exists, and the one rule for
the compile cache directory (rdma_paxos_tpu/utils/compile_cache.py)."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, env_extra, drop=(), timeout=120):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    for k in ("XLA_FLAGS",) + tuple(drop):
        env.pop(k, None)
    return subprocess.run([sys.executable] + args, cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=timeout)


def test_refuses_without_a_tpu():
    p = _run(["chip_smoke.py"], {})
    assert p.returncode not in (0, None), p.stdout
    assert "'cpu'" in p.stderr, p.stderr          # names what it found
    # no result line: nothing on stdout parses as the summary object
    for ln in p.stdout.splitlines():
        assert not ln.startswith("{"), ln


@pytest.mark.slow
def test_cpu_rehearsal_runs_every_phase():
    p = _run(["chip_smoke.py", "--rehearse-cpu"], {}, timeout=900)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    lines = p.stdout.strip().splitlines()
    # the result line carries exactly these keys (the chip check's shape)
    result = json.loads(lines[-1])
    assert set(result) == {"ok", "device"} and result["ok"] is True
    assert set(result["device"]) == {"platform", "kind", "count"}
    assert lines[-2].startswith("[summary] ")
    summary = json.loads(lines[-2][len("[summary] "):])
    assert summary["ok"] is True and summary["device"] == result["device"]
    assert summary["rehearsal"] is True and summary["platform"] == "cpu"
    assert summary["device"]["platform"] == "cpu"
    for name in ("build", "set", "kvs", "variants", "spmd_set",
                 "spmd_kvs", "mesh2x2"):
        assert summary["phases"][name]["ok"] is True, name
    assert len(set(summary["phases"]["spmd_kvs"]["table_devices"])) == 3


_PROBE = """
import json, jax
from rdma_paxos_tpu.utils.compile_cache import use_compile_cache
before = jax.config.jax_compilation_cache_dir
a, b = use_compile_cache(), use_compile_cache()
print(json.dumps(dict(before=before, a=a, b=b,
                      after=jax.config.jax_compilation_cache_dir)))
"""


def _probe(env_extra, drop=()):
    p = _run(["-c", _PROBE], env_extra, drop)
    assert p.returncode == 0, p.stderr
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_cache_dir_env_set_is_left_to_jax(tmp_path):
    d = str(tmp_path / "elsewhere")
    got = _probe({"JAX_COMPILATION_CACHE_DIR": d})
    # JAX read the variable itself; the function changed nothing
    assert got["before"] == got["after"] == got["a"] == got["b"] == d


def test_cache_dir_env_unset_is_the_checkout():
    drop = ("JAX_COMPILATION_CACHE_DIR",)
    one, two = _probe({}, drop), _probe({}, drop)     # two processes
    want = os.path.join(REPO, ".jax_cache")
    assert one["before"] is None
    assert one["a"] == one["b"] == one["after"] == want   # two calls
    assert two["a"] == want
