"""graftlint — the repo-native static analysis engine + runtime lock
sanitizer.

Three layers:

* per-pass fixture tests: each of the five passes catches a seeded
  synthetic violation (naming the exact file:line) and stays silent
  on a clean fixture — the analyzer's own regression harness;
* the live gate: ``run_analysis()`` on THIS checkout reports zero
  non-baselined findings (the CI ``analysis`` step runs the same
  command before pytest);
* the runtime sanitizer: under ``RP_SANITIZE=1`` a pipelined
  (pipeline=2) driver workload runs clean, while a deliberately
  unlocked mutation of a guarded field is caught at the exact access.
"""

import json
import os
import threading
import time

import pytest

from rdma_paxos_tpu.analysis import assert_jit_purity, run_analysis
from rdma_paxos_tpu.analysis.__main__ import main as lint_main
from rdma_paxos_tpu.analysis.engine import (
    Finding, PASS_IDS, Suppression, load_baseline, render_baseline,
    repo_root)
from rdma_paxos_tpu.analysis.runtime_guard import (
    LockDisciplineError, OwnedLock, guard, maybe_guard)


def _write(root, rel, text):
    path = os.path.join(root, rel)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w", encoding="utf-8") as f:
        f.write(text)
    return path


def _run(root, pass_id):
    return run_analysis(root=str(root), passes=(pass_id,),
                        baseline=None).findings


# ---------------------------------------------------------------------------
# jit-purity fixtures
# ---------------------------------------------------------------------------

def test_jit_purity_catches_direct_host_import(tmp_path):
    _write(tmp_path, "rdma_paxos_tpu/consensus/step.py",
           "import jax\nimport threading\n")
    fs = _run(tmp_path, "jit-purity")
    assert any(f.file == "rdma_paxos_tpu/consensus/step.py"
               and f.line == 2 and "threading" in f.message
               for f in fs), fs


def test_jit_purity_catches_transitive_obs_reachability(tmp_path):
    _write(tmp_path, "rdma_paxos_tpu/consensus/step.py",
           "from rdma_paxos_tpu.consensus import helper\n")
    _write(tmp_path, "rdma_paxos_tpu/consensus/helper.py",
           "import numpy\nfrom rdma_paxos_tpu.obs import metrics\n")
    fs = _run(tmp_path, "jit-purity")
    assert len(fs) == 1
    f = fs[0]
    # reported at the DEVICE module, chain names the indirection
    assert f.file == "rdma_paxos_tpu/consensus/step.py"
    assert f.line == 1
    assert "helper" in f.message and "rdma_paxos_tpu.obs" in f.message


def test_jit_purity_catches_source_pattern(tmp_path):
    _write(tmp_path, "rdma_paxos_tpu/parallel/mesh.py",
           "import jax\n\n\ndef f(state, obs):\n"
           "    obs.metrics.inc('boom')\n")
    fs = _run(tmp_path, "jit-purity")
    assert any(f.line == 5 and "metrics" in f.message for f in fs), fs


def test_jit_purity_catches_host_pure_regression(tmp_path):
    _write(tmp_path, "rdma_paxos_tpu/runtime/hostpath.py",
           "import numpy as np\nimport jax\n")
    fs = _run(tmp_path, "jit-purity")
    assert any(f.file == "rdma_paxos_tpu/runtime/hostpath.py"
               and f.line == 2 and "accelerator" in f.message
               for f in fs), fs


def test_jit_purity_silent_on_clean_fixture(tmp_path):
    _write(tmp_path, "rdma_paxos_tpu/consensus/step.py",
           "import jax\nimport jax.numpy as jnp\n"
           "from rdma_paxos_tpu.consensus.log import M_GIDX\n")
    _write(tmp_path, "rdma_paxos_tpu/consensus/log.py", "M_GIDX = 0\n")
    _write(tmp_path, "rdma_paxos_tpu/runtime/hostpath.py",
           "import numpy as np\n")
    assert _run(tmp_path, "jit-purity") == []


# ---------------------------------------------------------------------------
# cache-key fixtures
# ---------------------------------------------------------------------------

_BUILDER_BAD = """\
STEP_CACHE = {}


class Engine:
    def _build(self, elections):
        key = (self.cfg, self.R, elections)
        fn = STEP_CACHE.get(key)
        if fn is None:
            fn = build_step(self.cfg, self.R, audit=self._audit,
                            elections=elections)
            STEP_CACHE[key] = fn
        return fn
"""

_BUILDER_OK = _BUILDER_BAD.replace(
    "key = (self.cfg, self.R, elections)",
    "key = (self.cfg, self.R, elections)"
    " + (('audit',) if self._audit else ())")


def test_cache_key_catches_missing_flag(tmp_path):
    _write(tmp_path, "rdma_paxos_tpu/runtime/builder.py", _BUILDER_BAD)
    fs = _run(tmp_path, "cache-key")
    assert len(fs) == 1
    f = fs[0]
    assert f.file == "rdma_paxos_tpu/runtime/builder.py"
    assert "'_audit'" in f.message and f.line == 9, f


def test_cache_key_silent_when_flag_in_key(tmp_path):
    _write(tmp_path, "rdma_paxos_tpu/runtime/builder.py", _BUILDER_OK)
    assert _run(tmp_path, "cache-key") == []


def test_cache_key_clean_on_main_builders():
    """Every real STEP_CACHE builder folds every static flag it reads
    into its key, with zero baseline entries needed — and the engines'
    programs have ONE store site between them (``ClusterEngine.
    _program`` in runtime/sim.py, next to the redigest pass's and the
    replica mesh's; shard/cluster.py stores nothing)."""
    report = run_analysis(passes=("cache-key",), baseline=None)
    assert report.findings == [], [str(f) for f in report.findings]
    from rdma_paxos_tpu.analysis import cachekey
    from rdma_paxos_tpu.analysis.engine import SourceTree
    tree = SourceTree()
    sites = {rel: len(cachekey._store_sites(tree.module(rel)))
             for rel in ("rdma_paxos_tpu/runtime/sim.py",
                         "rdma_paxos_tpu/shard/cluster.py")}
    assert sites == {"rdma_paxos_tpu/runtime/sim.py": 3,
                     "rdma_paxos_tpu/shard/cluster.py": 0}, sites


# ---------------------------------------------------------------------------
# lock-discipline fixtures
# ---------------------------------------------------------------------------

_LOCKMOD_BAD = """\
import threading


class Engine:
    def __init__(self):
        self._host_lock = threading.RLock()
        self.pending = []       # guarded-by: _host_lock

    def good(self):
        with self._host_lock:
            return len(self.pending)

    def bad(self):
        self.pending.append(1)

    def also_fine_locked(self):
        return self.pending

    # holds-lock: _host_lock
    def documented(self):
        return self.pending
"""


def test_lock_discipline_flags_unlocked_access(tmp_path):
    _write(tmp_path, "rdma_paxos_tpu/runtime/sim.py", _LOCKMOD_BAD)
    fs = _run(tmp_path, "lock-discipline")
    assert len(fs) == 1
    f = fs[0]
    assert f.line == 14 and "bad()" in f.message and \
        "pending" in f.message, f


def test_lock_discipline_sees_the_lock_handed_to_a_profiled_take(tmp_path):
    """``with held(prof, self._host_lock, phase):`` (obs/spans.py: the
    take whose wait is a phase) holds the lock it is handed; a call
    handed another lock, or none, does not."""
    mod = _LOCKMOD_BAD.replace(
        "        with self._host_lock:\n",
        "        with held(self.prof, self._host_lock, 'fetch_lock_wait'):\n")
    assert mod != _LOCKMOD_BAD
    _write(tmp_path, "rdma_paxos_tpu/runtime/sim.py", mod)
    fs = _run(tmp_path, "lock-discipline")
    assert [f.line for f in fs] == [14], fs      # bad() alone, as before
    _write(tmp_path, "rdma_paxos_tpu/runtime/sim.py", mod.replace(
        "self._host_lock, 'fetch_lock_wait'", "self._other, 'x'"))
    assert sorted(f.line for f in _run(tmp_path, "lock-discipline")) \
        == [11, 14]


def test_lock_discipline_honors_writes_mode_and_conflict(tmp_path):
    mod = _LOCKMOD_BAD.replace("# guarded-by: _host_lock",
                               "# guarded-by: _host_lock [writes]")
    _write(tmp_path, "rdma_paxos_tpu/runtime/sim.py", mod)
    assert _run(tmp_path, "lock-discipline") == []   # reads exempt
    # conflicting re-declaration across modules is itself a finding
    _write(tmp_path, "rdma_paxos_tpu/runtime/driver.py",
           "class D:\n"
           "    def __init__(self):\n"
           "        self.pending = []   # guarded-by: _lock\n")
    fs = _run(tmp_path, "lock-discipline")
    assert any("re-declared" in f.message for f in fs), fs


# ---------------------------------------------------------------------------
# determinism fixtures
# ---------------------------------------------------------------------------

def test_determinism_catches_wall_clock_and_global_rng(tmp_path):
    _write(tmp_path, "rdma_paxos_tpu/chaos/faults.py",
           "import random\nimport time\n"
           "rng = random.Random('seed:1')\n"
           "def bad():\n"
           "    return time.time() + random.random()\n")
    fs = _run(tmp_path, "determinism")
    msgs = [f.message for f in fs]
    assert any("time.time" in m for m in msgs), msgs
    assert any("random.random" in m for m in msgs), msgs
    assert all(f.line == 5 for f in fs), fs   # Random('seed:1') legal


def test_determinism_catches_from_imports(tmp_path):
    """``from time import perf_counter`` is a bare Name at the call
    site — the import itself is flagged (post-review rider)."""
    _write(tmp_path, "rdma_paxos_tpu/chaos/faults.py",
           "from time import perf_counter\n"
           "from datetime import datetime\n"
           "from random import randint\n")
    fs = _run(tmp_path, "determinism")
    msgs = [f.message for f in fs]
    assert any("time.perf_counter" in m for m in msgs), msgs
    assert any("datetime.datetime" in m for m in msgs), msgs
    assert any("random.randint" in m for m in msgs), msgs
    assert [f.line for f in fs] == [1, 2, 3]


def test_determinism_silent_on_seeded_fixture(tmp_path):
    _write(tmp_path, "rdma_paxos_tpu/chaos/faults.py",
           "import random\nimport numpy as np\n"
           "rng = random.Random('x:3')\n"
           "g = np.random.default_rng(7)\n")
    assert _run(tmp_path, "determinism") == []


# ---------------------------------------------------------------------------
# thread-hygiene fixtures
# ---------------------------------------------------------------------------

def test_thread_hygiene_catches_unreaped_thread(tmp_path):
    _write(tmp_path, "rdma_paxos_tpu/obs/srv.py",
           "import threading\n"
           "def spawn(fn):\n"
           "    t = threading.Thread(target=fn)\n"
           "    t.start()\n"
           "    return t\n")
    fs = _run(tmp_path, "thread-hygiene")
    assert len(fs) == 1 and fs[0].line == 3, fs


def test_thread_hygiene_accepts_daemon_or_join(tmp_path):
    _write(tmp_path, "rdma_paxos_tpu/obs/srv.py",
           "import threading\n"
           "def spawn(fn):\n"
           "    t = threading.Thread(target=fn, daemon=True)\n"
           "    t.start()\n"
           "    u = threading.Thread(target=fn)\n"
           "    u.start()\n"
           "    u.join()\n")
    assert _run(tmp_path, "thread-hygiene") == []
    # post-construction daemon flag counts too (post-review rider)
    _write(tmp_path, "rdma_paxos_tpu/obs/srv.py",
           "import threading\n"
           "def spawn(fn):\n"
           "    t = threading.Thread(target=fn)\n"
           "    t.daemon = True\n"
           "    t.start()\n")
    assert _run(tmp_path, "thread-hygiene") == []


def test_thread_hygiene_string_join_blesses_nothing(tmp_path):
    """An unrelated ``self._sep.join(parts)`` string join must not
    count as a thread stop path (post-review rider)."""
    _write(tmp_path, "rdma_paxos_tpu/obs/srv.py",
           "import threading\n"
           "class S:\n"
           "    def spawn(self, fn):\n"
           "        self._w = threading.Thread(target=fn)\n"
           "        self._w.start()\n"
           "    def fmt(self, parts):\n"
           "        return self._sep.join(parts)\n")
    fs = _run(tmp_path, "thread-hygiene")
    assert len(fs) == 1 and fs[0].line == 4, fs
    # a join on the THREAD attribute is a stop path
    _write(tmp_path, "rdma_paxos_tpu/obs/srv.py",
           "import threading\n"
           "class S:\n"
           "    def spawn(self, fn):\n"
           "        self._w = threading.Thread(target=fn)\n"
           "        self._w.start()\n"
           "    def stop(self):\n"
           "        self._w.join()\n"
           "    def fmt(self, parts):\n"
           "        return self._sep.join(parts)\n")
    assert _run(tmp_path, "thread-hygiene") == []


def test_thread_hygiene_flags_bare_http_handler(tmp_path):
    _write(tmp_path, "rdma_paxos_tpu/obs/srv.py",
           "from http.server import BaseHTTPRequestHandler\n"
           "class H(BaseHTTPRequestHandler):\n"
           "    def do_GET(self):\n"
           "        self.wfile.write(b'x')\n")
    fs = _run(tmp_path, "thread-hygiene")
    assert len(fs) == 1 and "try/except" in fs[0].message, fs
    # wrapped body passes
    _write(tmp_path, "rdma_paxos_tpu/obs/srv.py",
           "from http.server import BaseHTTPRequestHandler\n"
           "class H(BaseHTTPRequestHandler):\n"
           "    def do_GET(self):\n"
           "        try:\n"
           "            self.wfile.write(b'x')\n"
           "        except Exception:\n"
           "            pass\n")
    assert _run(tmp_path, "thread-hygiene") == []


# ---------------------------------------------------------------------------
# baseline + CLI
# ---------------------------------------------------------------------------

def test_baseline_roundtrip_and_matching(tmp_path):
    entries = [Suppression(pass_id="determinism",
                           file="rdma_paxos_tpu/chaos/faults.py",
                           contains="time.time",
                           reason='has "quotes" and\nnewline')]
    path = tmp_path / "b.toml"
    path.write_text(render_baseline(entries, header="hdr"))
    back = load_baseline(str(path))
    assert len(back) == 1
    assert back[0].contains == "time.time"
    assert back[0].reason == 'has "quotes" and\nnewline'
    f = Finding(file="rdma_paxos_tpu/chaos/faults.py", line=3,
                pass_id="determinism", message="wall clock time.time")
    assert back[0].matches(f)
    assert not back[0].matches(
        Finding(file="other.py", line=3, pass_id="determinism",
                message="wall clock time.time"))


def test_baseline_symbol_pins_field_and_function(tmp_path):
    """A lock-discipline suppression with ``symbol`` excuses ONLY the
    (field, function) pair it was triaged for — a different field's
    unlocked access in the same function stays a failure
    (post-review rider: function-only matching silently blessed the
    exact race class the pass exists to catch)."""
    s = Suppression(pass_id="lock-discipline", file="f.py",
                    contains="read of '_tickets'",
                    symbol="block in step()", reason="peek")
    excused = Finding(file="f.py", line=9, pass_id="lock-discipline",
                      message="read of '_tickets' (guarded-by x) "
                              "outside a `with ...x` block in step()")
    other_field = Finding(file="f.py", line=9,
                          pass_id="lock-discipline",
                          message="write of 'last' (guarded-by x) "
                                  "outside a `with ...x` block in "
                                  "step()")
    other_fn = Finding(file="f.py", line=9, pass_id="lock-discipline",
                       message="read of '_tickets' (guarded-by x) "
                               "outside a `with ...x` block in "
                               "drain()")
    assert s.matches(excused)
    assert not s.matches(other_field)
    assert not s.matches(other_fn)


def test_write_baseline_appends_preserving_comments(tmp_path):
    """--write-baseline APPENDS stubs — curated comments and section
    headers in the checked-in baseline survive a triage round
    (post-review rider: the old load/render round-trip destroyed
    them)."""
    _write(tmp_path, "rdma_paxos_tpu/chaos/faults.py",
           "import time\nT = time.time\n")
    base = tmp_path / "b.toml"
    base.write_text("# hand-curated header\n"
                    "# ---- section marker ----\n")
    rc = lint_main(["--root", str(tmp_path), "--baseline", str(base),
                    "--write-baseline", "-q", "determinism"])
    assert rc == 1
    text = base.read_text()
    assert "# hand-curated header" in text
    assert "# ---- section marker ----" in text
    assert len(load_baseline(str(base))) == 1


def test_baseline_rejects_malformed(tmp_path):
    p = tmp_path / "b.toml"
    p.write_text("[[suppress]]\npass = unquoted\n")
    with pytest.raises(ValueError):
        load_baseline(str(p))
    p.write_text('[[suppress]]\npass = "x"\n')   # missing keys
    with pytest.raises(ValueError):
        load_baseline(str(p))


def test_cli_exit_semantics_and_json(tmp_path, capsys):
    _write(tmp_path, "rdma_paxos_tpu/chaos/faults.py",
           "import time\nT = time.time\n")
    out_json = str(tmp_path / "findings.json")
    rc = lint_main(["--root", str(tmp_path), "--no-baseline",
                    "--json", out_json, "determinism"])
    assert rc == 1
    doc = json.load(open(out_json))
    assert doc["ok"] is False and len(doc["findings"]) == 1
    printed = capsys.readouterr().out
    assert "rdma_paxos_tpu/chaos/faults.py:2" in printed
    # a baselined finding exits 0 and lands in `suppressed`
    base = tmp_path / "b.toml"
    base.write_text(render_baseline([Suppression(
        pass_id="determinism",
        file="rdma_paxos_tpu/chaos/faults.py",
        contains="time.time", reason="fixture")]))
    rc = lint_main(["--root", str(tmp_path), "--baseline", str(base),
                    "--json", out_json, "determinism"])
    assert rc == 0
    doc = json.load(open(out_json))
    assert doc["ok"] is True and len(doc["suppressed"]) == 1


def test_cli_write_baseline_records_stubs(tmp_path, capsys):
    _write(tmp_path, "rdma_paxos_tpu/chaos/faults.py",
           "import time\nT = time.time\n")
    base = str(tmp_path / "b.toml")
    rc = lint_main(["--root", str(tmp_path), "--baseline", base,
                    "--write-baseline", "determinism"])
    assert rc == 1                  # recording does not bless
    entries = load_baseline(base)
    assert len(entries) == 1
    rc = lint_main(["--root", str(tmp_path), "--baseline", base,
                    "determinism"])
    assert rc == 0                  # now suppressed


# ---------------------------------------------------------------------------
# the live gate: this checkout is clean
# ---------------------------------------------------------------------------

def test_graftlint_clean_on_this_checkout():
    """The CI gate, in-process: all five passes over the real tree,
    checked-in baseline applied — zero live findings, zero unused
    suppressions, and the budget holds with two orders of margin."""
    t0 = time.monotonic()
    report = run_analysis()
    dt = time.monotonic() - t0
    assert report.findings == [], [str(f) for f in report.findings]
    assert report.unused_suppressions == [], [
        (s.pass_id, s.file, s.contains)
        for s in report.unused_suppressions]
    assert report.suppressed, "baseline should be exercised"
    assert dt < 60.0, "analysis must stay under the CI budget"
    assert set(PASS_IDS) == {
        "jit-purity", "cache-key", "lock-discipline", "determinism",
        "thread-hygiene"}


def test_jit_purity_wrapper_contract():
    """The helper the six tier-1 jit-safety wrappers call."""
    assert_jit_purity()            # must not raise on this checkout
    assert os.path.isdir(os.path.join(repo_root(), "rdma_paxos_tpu"))


# ---------------------------------------------------------------------------
# runtime sanitizer: unit level
# ---------------------------------------------------------------------------

class _Toy:
    def __init__(self):
        self._lock = threading.Lock()
        self.q = []            # write-guarded in the tests below
        self.name = "free"


def test_owned_lock_tracks_ownership():
    lk = OwnedLock()
    assert not lk._is_owned()
    with lk:
        assert lk._is_owned() and lk.locked()
        seen = []
        t = threading.Thread(
            target=lambda: seen.append(lk._is_owned()))
        t.start()
        t.join()
        assert seen == [False]   # held, but not by THAT thread
    assert not lk._is_owned() and not lk.locked()


def test_guard_write_and_strict_read_checks():
    obj = _Toy()
    guard(obj, "_lock", write_fields=("q",), read_fields=("q",))
    assert type(obj).__name__ == "_Toy+sanitized"
    with pytest.raises(LockDisciplineError):
        obj.q = [1]
    with pytest.raises(LockDisciplineError):
        len(obj.q)
    with obj._lock:
        obj.q = [1]
        assert len(obj.q) == 1
    obj.name = "still-free"      # unguarded fields stay unchecked


def test_maybe_guard_noop_without_env(monkeypatch):
    monkeypatch.delenv("RP_SANITIZE", raising=False)
    obj = _Toy()
    maybe_guard(obj, "_lock", __file__)
    assert type(obj).__name__ == "_Toy"
    obj.q = [2]                  # unchecked


# ---------------------------------------------------------------------------
# runtime sanitizer: the tier-1 pipelined regression
# ---------------------------------------------------------------------------

def test_sanitized_pipelined_driver_workload(monkeypatch):
    """A pipeline=2 driver workload runs CLEAN under RP_SANITIZE=1 —
    every guarded write in the dispatch/readback split holds its
    declared lock — while a deliberately unlocked test-injected
    mutation is caught at the exact access."""
    from rdma_paxos_tpu.config import LogConfig, TimeoutConfig
    from rdma_paxos_tpu.runtime.driver import ClusterDriver

    monkeypatch.setenv("RP_SANITIZE", "1")
    cfg = LogConfig(n_slots=128, slot_bytes=64, window_slots=32,
                    batch_slots=8)
    to = TimeoutConfig(elec_timeout_low=1e9, elec_timeout_high=2e9)
    d = ClusterDriver(cfg, 3, timeout_cfg=to, pipeline=2)
    try:
        assert type(d.cluster).__name__ == "SimCluster+sanitized"
        d.cluster.run_until_elected(0)
        d.step()
        assert d.leader() == 0
        handler = d._make_handler(0)
        conn = (0 << 24) | 31
        assert not isinstance(handler(2, conn, b""), int)
        # pre-queued record sized past one fused burst (the
        # test_pipeline overlap recipe) so pipelining engages
        evs = [handler(3, conn, b"s%03d" % i) for i in range(160)]
        d.run(period=0.001)
        for i, ev in enumerate(evs):
            assert ev.done.wait(30), f"ack {i} never released"
            assert ev.status == 0, (i, ev.status)
    finally:
        d.stop()
    assert d.loop_error is None, d.loop_error
    assert d.cluster.max_inflight_dispatches >= 2, (
        "pipelining never engaged — the sanitize run must cover the "
        "dispatch/readback overlap")
    # the deliberate race: mutate a guarded field off-lock
    with pytest.raises(LockDisciplineError):
        d.cluster.pending = [[] for _ in range(3)]
    with d.cluster._host_lock:
        d.cluster.pending = [[] for _ in range(3)]


def test_sanitized_read_hub_strict(monkeypatch):
    """ReadHub._q is declared [strict]: under RP_SANITIZE=1 even a
    lock-free READ trips the sanitizer."""
    monkeypatch.setenv("RP_SANITIZE", "1")
    from rdma_paxos_tpu.runtime.reads import ReadHub
    hub = ReadHub()
    assert hub.pending_count() == 0      # locked read path stays fine
    with pytest.raises(LockDisciplineError):
        len(hub._q)
