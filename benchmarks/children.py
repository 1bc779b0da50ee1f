"""Parent side of the load generator: start the child processes, hand
each its plan, release them together, collect their records, and make
sure none outlives the run."""

from __future__ import annotations

import json
import os
import queue
import subprocess
import sys
import threading
import time
from typing import Any, Dict, List


class Children:
    """The load-generator children of one window."""

    def __init__(self, plans: List[Dict[str, Any]], root: str) -> None:
        # the children never import JAX; the variable only makes sure a
        # stray import could not reach for the chip this process holds
        env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONUNBUFFERED="1")
        self._procs: List[subprocess.Popen] = []
        self._lines: List["queue.Queue[str]"] = []
        for plan in plans:
            proc = subprocess.Popen(
                [sys.executable, "-m", "benchmarks.loadgen"], cwd=root,
                env=env, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                text=True)
            lines: "queue.Queue[str]" = queue.Queue()
            threading.Thread(target=self._pump, args=(proc, lines),
                             daemon=True).start()
            proc.stdin.write(json.dumps(plan) + "\n")
            proc.stdin.flush()
            self._procs.append(proc)
            self._lines.append(lines)

    @staticmethod
    def _pump(proc: subprocess.Popen, lines: "queue.Queue[str]") -> None:
        for line in proc.stdout:
            lines.put(line)
        lines.put("")                       # end of the child's output

    def _next(self, i: int, deadline: float) -> str:
        try:
            line = self._lines[i].get(
                timeout=max(0.1, deadline - time.monotonic()))
        except queue.Empty:
            line = ""
        if not line:
            self.stop()
            raise RuntimeError(f"load generator {i} ended or fell silent "
                               f"(exit code {self._procs[i].poll()})")
        return line

    def wait_ready(self, timeout: float = 60.0) -> None:
        """Every child has built its clients and dialled them."""
        deadline = time.monotonic() + timeout
        for i in range(len(self._procs)):
            if self._next(i, deadline).strip() != "ready":
                self.stop()
                raise RuntimeError(f"load generator {i} is not ready")

    def go(self, t0: float) -> None:
        for proc in self._procs:
            proc.stdin.write(f"go {t0!r}\n")
            proc.stdin.flush()

    def collect(self, deadline: float) -> List[Dict[str, Any]]:
        """Every child's records, once each has printed its result."""
        records: List[Dict[str, Any]] = []
        for i in range(len(self._procs)):
            records.extend(json.loads(self._next(i, deadline))["records"])
        self.stop()
        return records

    def stop(self) -> None:
        """End every child and wait for it (a finished child just
        exits; one still running is killed)."""
        for proc in self._procs:
            if proc.poll() is None:
                try:
                    proc.stdin.close()
                except OSError:
                    pass
                try:
                    proc.wait(timeout=5)
                except subprocess.TimeoutExpired:
                    proc.kill()
                    proc.wait()
            if proc.stdout is not None:
                proc.stdout.close()
