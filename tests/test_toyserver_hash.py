"""``native/toyserver``'s hash records (``HMSET``/``HGETALL``, what
YCSB's Redis binding sends) alone and beside ``SET``/``GET``, and a full
table answered with an error instead of a dropped write."""

import os
import socket
import subprocess
import time

import pytest

NATIVE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "native")
MAXKV = 131072          # toyserver.c


@pytest.fixture(scope="module")
def app():
    subprocess.run(["make", "-C", NATIVE], check=True, capture_output=True)
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    proc = subprocess.Popen([os.path.join(NATIVE, "toyserver"), str(port)],
                            stderr=subprocess.DEVNULL)
    for _ in range(100):
        try:
            socket.create_connection(("127.0.0.1", port)).close()
            break
        except ConnectionRefusedError:
            time.sleep(0.02)
    yield port
    proc.kill()
    proc.wait()


@pytest.fixture()
def cmd(app):
    s = socket.create_connection(("127.0.0.1", app), timeout=10)
    f = s.makefile("rb")

    def ask(line: str) -> bytes:
        s.sendall(line.encode() + b"\n")
        return f.readline().rstrip(b"\n")
    yield ask
    s.close()


def test_hmset_creates_updates_and_lists_in_field_order(cmd):
    assert cmd("HGETALL h1") == b"-"
    assert cmd("HMSET h1 field0 aaa field1 bbb") == b"+OK"
    assert cmd("HGETALL h1") == b"field0 aaa field1 bbb"
    assert cmd("HMSET h1 field1 ccc") == b"+OK"             # one field
    assert cmd("HGETALL h1") == b"field0 aaa field1 ccc"
    assert cmd("HMSET h1 field2 ddd field0 eee") == b"+OK"  # new + old
    assert cmd("HGETALL h1") == b"field0 eee field1 ccc field2 ddd"


def test_ycsb_record_ten_fields_of_100_bytes(cmd):
    vals = [(b"%d" % j) * 100 for j in range(10)]
    line = "HMSET ycsb " + " ".join(
        f"field{j} {v.decode()}" for j, v in enumerate(vals))
    assert len(line) > 1024                 # more than two 512-byte slots
    assert cmd(line) == b"+OK"
    got = cmd("HGETALL ycsb").split(b" ")
    assert got[::2] == [b"field%d" % j for j in range(10)]
    assert got[1::2] == vals
    assert cmd("HMSET ycsb field7 " + "z" * 100) == b"+OK"
    assert cmd("HGETALL ycsb").split(b" ")[15] == b"z" * 100


def test_hmset_is_all_or_nothing(cmd):
    assert cmd("HMSET h2 a 1") == b"+OK"
    assert cmd("HMSET h2 b") == b"-ERR"                 # a field, no value
    assert cmd("HMSET h2") == b"-ERR"
    assert cmd("HMSET h3 a") == b"-ERR"
    assert cmd("HGETALL h3") == b"-"                    # not created
    many = " ".join(f"f{j} v" for j in range(16))
    assert cmd("HMSET h2 " + many) == b"-ERR full"      # 17 fields
    assert cmd("HGETALL h2") == b"a 1"
    assert cmd("HMSET h2 a 2 a 3") == b"+OK"            # the later wins
    assert cmd("HGETALL h2") == b"a 3"


def test_strings_and_hashes_side_by_side(cmd):
    n0 = int(cmd("COUNT"))
    assert cmd("SET s1 plain") == b"+OK"
    assert cmd("HMSET r1 f v") == b"+OK"
    assert int(cmd("COUNT")) == n0 + 2          # keys of both kinds
    assert cmd("GET s1") == b"plain" and cmd("HGETALL r1") == b"f v"
    assert cmd("GET r1") == b"-" and cmd("HGETALL s1") == b"-"
    assert cmd("HMSET s1 f v") == b"-ERR wrongtype"
    assert cmd("SET r1 x") == b"-ERR wrongtype"
    assert cmd("DEL s1") == b"+OK" and cmd("GET s1") == b"-"
    assert cmd("ECHO tok") == b"=tok"
    assert int(cmd("COUNT")) == n0 + 1


def test_full_table_says_so(app):
    """A SET the table has no room for is an error, not a ``+OK`` over a
    dropped write (keys that never repeat fill it in half a minute at a
    few thousand operations a second)."""
    s = socket.create_connection(("127.0.0.1", app), timeout=60)
    f = s.makefile("rb")
    s.sendall(b"COUNT\n")
    s.sendall(b"".join(b"SET fill%d x\n" % i for i in range(MAXKV)))
    held = int(f.readline())
    replies = [f.readline().rstrip(b"\n") for _ in range(MAXKV)]
    assert set(replies) == {b"+OK", b"-ERR full"}
    # the table holds MAXKV - 1 string keys (hash records have their own)
    assert MAXKV - 1 - held <= replies.count(b"+OK") <= MAXKV - 1
    first_full = replies.index(b"-ERR full")
    assert all(r == b"-ERR full" for r in replies[first_full:])
    s.sendall(b"GET fill0\nGET fill%d\nSET fill0 y\nGET fill0\n"
              % (MAXKV - 1))
    assert [f.readline().rstrip(b"\n") for _ in range(4)] == [
        b"x", b"-", b"+OK", b"y"]               # an update still fits
    s.close()
