"""Build what git ignores: ``native/*.so`` and ``native/toyserver``.

The first run in a checkout builds them with the repo's own Makefile
(``make -C native``, no ``clean``); later runs find them and ``make``
has nothing to do."""

from __future__ import annotations

import os
import subprocess
import time


def ensure_built(root: str) -> float:
    """-> seconds spent (a fraction of a second when up to date)."""
    native = os.path.join(root, "native")
    t0 = time.monotonic()
    proc = subprocess.run(["make", "-C", native], capture_output=True,
                          timeout=600)
    if proc.returncode != 0:
        raise RuntimeError(
            "make -C native failed:\n"
            + proc.stderr.decode(errors="replace")[-2000:])
    return time.monotonic() - t0
