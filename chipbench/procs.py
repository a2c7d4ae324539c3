"""Child processes of a run: output to files in the run directory, a
line to wait for, and a stop that always reaps."""

from __future__ import annotations

import os
import signal
import subprocess
import time


class Child:
    """One child whose stdout and stderr go to ``<run_dir>/<name>.out`` and
    ``.err`` (no pipe to fill, nothing of it reaches the parent's stdout)."""

    def __init__(self, name: str, argv: list[str], env: dict, run_dir: str, cwd: str):
        self.name = name
        self.out_path = os.path.join(run_dir, f"{name}.out")
        self.err_path = os.path.join(run_dir, f"{name}.err")
        self._out = open(self.out_path, "wb")
        self._err = open(self.err_path, "wb")
        self.proc = subprocess.Popen(
            argv, cwd=cwd, env=env, stdin=subprocess.DEVNULL,
            stdout=self._out, stderr=self._err, start_new_session=True,
        )

    def read_err(self) -> str:
        with open(self.err_path, "rb") as f:
            return f.read().decode(errors="replace")

    def read_out(self) -> str:
        with open(self.out_path, "rb") as f:
            return f.read().decode(errors="replace")

    def wait_for_line(self, needle: str, deadline: float, stream: str = "err") -> str | None:
        """The first line of the stream that contains ``needle``; None when
        the child exits or ``deadline`` (monotonic) passes first."""
        path = self.err_path if stream == "err" else self.out_path
        while True:
            with open(path, "rb") as f:
                for raw in f:
                    line = raw.decode(errors="replace")
                    if needle in line and line.endswith("\n"):
                        return line.rstrip("\n")
            if self.proc.poll() is not None or time.monotonic() > deadline:
                return None
            time.sleep(0.05)

    def wait(self, timeout: float) -> int | None:
        try:
            return self.proc.wait(timeout)
        except subprocess.TimeoutExpired:
            return None

    def stop(self, sig: int = signal.SIGTERM, grace: float = 30.0) -> int | None:
        """Signal, wait, kill the whole session if it outlives ``grace``;
        always reaped on return."""
        if self.proc.poll() is None:
            try:
                self.proc.send_signal(sig)
                self.proc.wait(grace)
            except subprocess.TimeoutExpired:
                pass
            except ProcessLookupError:
                pass
        if self.proc.poll() is None:
            try:
                os.killpg(self.proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            self.proc.wait(10)
        else:
            # Grandchildren, if any, die with the session.
            try:
                os.killpg(self.proc.pid, signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                pass
        for f in (self._out, self._err):
            if not f.closed:
                f.close()
        return self.proc.returncode

    def tail(self, n: int = 30) -> str:
        lines = [
            l for l in self.read_err().splitlines()
            if "Finished " not in l and "Compiling " not in l
        ]
        return "\n".join(f"  [{self.name}] {l}" for l in lines[-n:])
