"""The pipelined load generator's child (``perfbench/generators/
resp_pipelined.py``) against a bare ``native/toyserver``: whole batches
of sixteen, a stamp a reply, and every key and value drawn again from the
seed by the parent's side."""

import collections
import json
import os
import socket
import subprocess
import sys
import time

import pytest

from perfbench.generators import resp_closed_loop, resp_pipelined as gen

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NATIVE = os.path.join(ROOT, "native")
PARAMS = dict(connections=5, pipeline=16, keyspace=2000, value_bytes=3,
              grace_s=5.0)
SEED = 2147483659           # past 32 signed bits, as the driver's are


@pytest.fixture(scope="module")
def sample(tmp_path_factory):
    subprocess.run(["make", "-C", NATIVE], check=True, capture_output=True)
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    app = subprocess.Popen([os.path.join(NATIVE, "toyserver"), str(port),
                            "-s", "12"], stderr=subprocess.DEVNULL)
    out = str(tmp_path_factory.mktemp("gen") / "sample.bin")
    try:
        for _ in range(100):
            try:
                socket.create_connection(("127.0.0.1", port)).close()
                break
            except ConnectionRefusedError:
                time.sleep(0.02)
        child = subprocess.Popen(
            [sys.executable, gen.__file__, "--port", str(port),
             "--params", json.dumps(PARAMS), "--seed", str(SEED),
             "--out", out], stdin=subprocess.PIPE, stdout=subprocess.PIPE)
        time.sleep(0.5)
        child.stdin.write(b"stop\n")
        child.stdin.flush()
        progress = child.stdout.read().decode().split()
        assert child.wait(timeout=30) == 0
        cols = resp_closed_loop.read_sample_file(out)
        with socket.create_connection(("127.0.0.1", port)) as s:
            f = s.makefile("rb")

            def ask(line):
                s.sendall(line + b"\n")
                return f.readline().strip()
            keys, values = gen.writes_of(PARAMS, SEED, cols["conn"],
                                         cols["idx"])
            held = {k: ask(b"GET " + gen.key_of(k)) for k in set(keys)}
            count = int(ask(b"COUNT"))
        yield dict(cols=cols, keys=keys, values=values, held=held,
                   count=count, progress=progress)
    finally:
        app.kill()
        app.wait()


def test_batches_are_whole_and_every_request_is_answered(sample):
    cols = sample["cols"]
    n = cols["header"]["n_ops"]
    assert n > 16 * PARAMS["connections"] and n % 16 == 0
    assert set(cols["state"]) == {gen.OK}
    assert sample["progress"][-2:] == ["D", str(n)]
    per_conn = collections.defaultdict(list)
    for k in range(n):
        per_conn[cols["conn"][k]].append(k)
    assert sorted(per_conn) == list(range(PARAMS["connections"]))
    for conn, rows in per_conn.items():
        # its indices run 0, 1, 2, ... in the order sent
        assert [cols["idx"][k] for k in rows] == list(range(len(rows)))
        for at in range(0, len(rows), 16):
            batch = rows[at:at + 16]
            # sixteen rows, neighbours in the table, ONE request stamp
            assert batch == list(range(batch[0], batch[0] + 16))
            assert len({cols["send"][k] for k in batch}) == 1
            # a stamp a reply: none before the batch's write, in order,
            # and the next batch is written only after the sixteenth
            recv = [cols["recv"][k] for k in batch]
            assert recv == sorted(recv) and recv[0] >= cols["send"][batch[0]]
            if at + 16 < len(rows):
                assert cols["send"][rows[at + 16]] >= recv[-1]


def test_keys_and_values_are_the_seeds(sample):
    """The parent draws every stream again: the app holds, for every key
    the child wrote, a value some write of that key carried, and nothing
    else; keys are ``key:<12 digits>`` under the keyspace, values three
    letters."""
    written = collections.defaultdict(set)
    for k, v in zip(sample["keys"], sample["values"]):
        assert 0 <= k < PARAMS["keyspace"]
        assert len(v) == 3 and v.decode().isalnum()
        written[k].add(v)
    assert gen.key_of(7) == b"key:000000000007"
    assert sample["count"] == len(written)
    assert all(sample["held"][k] in vs for k, vs in written.items())
    # connections draw streams of their own and still meet on keys
    by_conn = collections.defaultdict(set)
    for c, k in zip(sample["cols"]["conn"], sample["keys"]):
        by_conn[c].add(k)
    assert len(set.intersection(*by_conn.values())) > 0
    # another seed, other keys
    other, _ = gen.writes_of(PARAMS, SEED + 1, sample["cols"]["conn"],
                             sample["cols"]["idx"])
    assert other != sample["keys"]


def test_draw_is_one_number_a_set():
    import random
    draw = gen.drawer(1000000, 3)
    a, b = random.Random("s"), random.Random("s")
    alphabet = resp_closed_loop.ALPHABET
    for _ in range(100):
        key, value = draw(a)
        x = int(b.random() * 1000000 * 36 ** 3)
        assert key == x // 36 ** 3 and 0 <= key < 1000000
        n = x % 36 ** 3
        assert value.decode() == (alphabet[n // 1296]
                                  + alphabet[n // 36 % 36] + alphabet[n % 36])
    # every value of the alphabet's, no two alike
    assert len({gen.drawer(10, 2)(random.Random(i))[1]
                for i in range(20000)}) == 36 ** 2
