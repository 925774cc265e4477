"""Child processes of one benchmark run: spawn with a log, wait for /health,
stop the whole process group.

Copied from chip_smoke.py's helpers rather than imported: the yardstick must
not move when the program does. This module never imports JAX; a parent that
touches JAX would hold the chip its children need.
"""

from __future__ import annotations

import dataclasses
import json
import os
import signal
import subprocess
import sys
import time
import urllib.error
import urllib.request

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class BenchFailure(Exception):
    """The run cannot produce a result; the message is printed and the
    process exits non-zero."""


@dataclasses.dataclass
class Child:
    name: str
    proc: subprocess.Popen
    started: float
    log_path: str

    def log_tail(self, n: int = 2000) -> str:
        try:
            with open(self.log_path, errors="replace") as f:
                return f.read()[-n:]
        except OSError:
            return ""


class Procs:
    """Every process a run starts; stop() ends each and waits for it."""

    def __init__(self, log_dir: str):
        self.log_dir = log_dir
        self.children: list[Child] = []
        os.makedirs(log_dir, exist_ok=True)

    def start(self, name: str, argv: list[str],
              env: dict[str, str] | None = None) -> Child:
        full_env = dict(os.environ)
        full_env["PYTHONPATH"] = ROOT + os.pathsep + full_env.get("PYTHONPATH", "")
        full_env.update(env or {})
        log_path = os.path.join(self.log_dir, f"{name}.log")
        with open(log_path, "w") as log:
            proc = subprocess.Popen(
                [sys.executable, *argv], cwd=ROOT, env=full_env, stdout=log,
                stderr=subprocess.STDOUT, start_new_session=True)
        child = Child(name, proc, time.monotonic(), log_path)
        self.children.append(child)
        return child

    def stop(self) -> dict[str, dict]:
        """SIGTERM to each group, SIGKILL to what is left after the engine's
        own drain limit; returns how each child ended."""
        t0 = time.monotonic()
        ended = {}
        for child in self.children:
            if child.proc.poll() is None:
                try:
                    os.killpg(child.proc.pid, signal.SIGTERM)
                except ProcessLookupError:  # exited since the poll
                    pass
        deadline = t0 + 40.0
        for child in self.children:
            killed = False
            try:
                child.proc.wait(timeout=max(0.1, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                killed = True
                try:
                    os.killpg(child.proc.pid, signal.SIGKILL)
                except ProcessLookupError:
                    pass
                child.proc.wait(timeout=10)
            ended[child.name] = {"seconds": time.monotonic() - t0,
                                 "killed": killed}
        self.children = []
        return ended


def http_get(url: str, timeout: float = 5.0) -> tuple[int, bytes]:
    """Blocking GET for set-up and scrapes (the load itself uses httpx)."""
    try:
        with urllib.request.urlopen(url, timeout=timeout) as r:
            return r.status, r.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


def wait_healthy(child: Child, url: str, timeout_s: float) -> tuple[dict, float]:
    """Poll url until it answers 200; returns (body, seconds since the child
    was started). A child that exits first has refused to start."""
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if child.proc.poll() is not None:
            raise BenchFailure(
                f"{child.name} exited with {child.proc.returncode}: "
                f"{child.log_tail()}")
        try:
            status, body = http_get(url)
            if status == 200:
                return json.loads(body), time.monotonic() - child.started
        except (OSError, ValueError):  # not listening yet
            pass
        time.sleep(0.25)
    raise BenchFailure(f"{url} not healthy after {timeout_s:.0f} s: "
                       f"{child.log_tail()}")
