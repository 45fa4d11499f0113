"""Whether the host's TPU device nodes are free: a run starts its worker only
when they are, and leaves them so.

A chip belongs to one process at a time. The runtime opens one node a chip
(``/dev/vfio/<n>`` on a v5e host, ``/dev/accel<n>`` on older ones: the
inventory ``tpu9/worker/tpu_manager.py`` reads) and a second ``open()`` fails
with ``EBUSY`` until the first holder has let go — which, on four chips, is
seconds AFTER its process tree has gone (PR 32: a run started <= 2 s after the
last one's exit lost its first replica to ``open(/dev/vfio/<n>): Device or
resource busy``). So the probe is that same ``open()``, and a walk of
``/proc/*/fd`` beside it, which names the holder where there is one.

The probe holds a free node for the microseconds between its ``open()`` and
its ``close()``: it runs before the worker starts and after the process trees
have ended, never beside a runner that is starting.
"""

from __future__ import annotations

import errno
import glob
import os
import time

WAIT_S = 60.0
POLL_S = 0.25


class ChipsBusy(RuntimeError):
    pass


def nodes(dev: str = "/dev") -> list:
    """The device nodes, one a chip, as the worker's inventory finds them."""
    return sorted(glob.glob(os.path.join(dev, "accel*"))) or sorted(
        glob.glob(os.path.join(dev, "vfio", "[0-9]*")))


def holders(paths: list) -> dict:
    """``{node: [(pid, command line), ...]}`` for every process but this one
    that holds one of ``paths`` open, as far as ``/proc`` lets this user see."""
    want = {os.path.realpath(p): p for p in paths}
    found: dict = {}
    for entry in os.listdir("/proc"):
        if not entry.isdigit() or int(entry) == os.getpid():
            continue
        try:
            fds = os.listdir(f"/proc/{entry}/fd")
        except OSError:
            continue
        for fd in fds:
            try:
                node = want.get(os.readlink(f"/proc/{entry}/fd/{fd}"))
            except OSError:
                continue
            if node is None:
                continue
            try:
                with open(f"/proc/{entry}/cmdline", "rb") as f:
                    cmd = f.read().replace(b"\0", b" ").decode(
                        errors="replace").strip()
            except OSError:
                cmd = ""
            found.setdefault(node, []).append((int(entry), cmd[:200]))
            break
    return found


def refuses_open(path: str) -> bool:
    """Whether the node answers ``open()`` as it answers a second runtime."""
    try:
        fd = os.open(path, os.O_RDWR)
    except OSError as exc:
        return exc.errno == errno.EBUSY
    os.close(fd)
    return False


def busy(dev: str = "/dev") -> dict:
    """``{node: [(pid, command line), ...]}`` of the nodes that are held: by
    a process that ``/proc`` shows, or (an empty list) by one it does not."""
    paths = nodes(dev)
    held = holders(paths)
    for path in paths:
        if path not in held and refuses_open(path):
            held[path] = []
    return held


def describe(held: dict) -> str:
    return "; ".join(
        f"{node} held by " + (", ".join(f"pid {pid} ({cmd})"
                                        for pid, cmd in who)
                              or "no process /proc shows")
        for node, who in sorted(held.items()))


def wait_free(timeout: float = WAIT_S, dev: str = "/dev",
              poll: float = POLL_S) -> float:
    """Block until no node is held; the seconds that took (0.0 where the
    first look finds them free, or the host has none). ``ChipsBusy`` names
    what stayed busy, and who held it, after ``timeout`` seconds."""
    t0 = time.monotonic()
    held = busy(dev)
    if not held:
        return 0.0
    while time.monotonic() - t0 < timeout:
        time.sleep(poll)
        held = busy(dev)
        if not held:
            return time.monotonic() - t0
    raise ChipsBusy(f"after {timeout:.0f} s still busy: {describe(held)}")
