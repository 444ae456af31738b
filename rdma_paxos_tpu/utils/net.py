"""Socket helpers shared by the host-side servers."""

from __future__ import annotations

import socket
import threading
from typing import Optional


def close_listener(srv: socket.socket,
                   thread: Optional[threading.Thread],
                   join_timeout: float = 2.0) -> None:
    """Close a listening socket AND end the thread blocked in its
    ``accept()``. On Linux ``close()`` from another thread leaves a
    blocked ``accept()`` asleep on the old file description, so the
    acceptor outlives its server; ``shutdown()`` wakes it with an
    error first."""
    try:
        srv.shutdown(socket.SHUT_RDWR)
    except OSError:
        pass
    try:
        srv.close()
    except OSError:
        pass
    if thread is not None and thread is not threading.current_thread():
        thread.join(timeout=join_timeout)
