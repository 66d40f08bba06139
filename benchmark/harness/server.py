"""The `serened` child: start, ready line, control channel, stop.

The parent never imports jax. The child is `serve_child.py`, which runs
`serenedb_tpu.serened.main` unchanged (ports 0, a fresh datadir, the
configuration's server settings as `SERENE_*` environment).
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CHILD = os.path.join(HERE, "serve_child.py")


class ServerError(Exception):
    pass


class Server:
    def __init__(self, datadir: str, log_path: str, env: dict,
                 child: str = CHILD):
        self.datadir = datadir
        self.log_path = log_path
        self.env = env
        self.child = child
        self.proc = None
        self.pg_port = self.http_port = 0
        self.backend: dict = {}
        self.cache_dir = ""
        self._tag = 0
        self._read_to = 0           # bytes of the log already split
        self._log_lines: list[str] = []

    def launch(self) -> None:
        """Start the child; `wait_ready` reads its ready line (the data
        is generated in between, while the backend boots)."""
        env = dict(os.environ)
        env.update(self.env)
        env["PYTHONUNBUFFERED"] = "1"
        env.pop("BENCH_RUN", None)
        self._log = open(self.log_path, "w")
        self.proc = subprocess.Popen(
            [sys.executable, self.child, self.datadir], cwd=ROOT, env=env,
            stdin=subprocess.PIPE, stdout=self._log,
            stderr=subprocess.STDOUT, text=True)

    def wait_ready(self, timeout_s: float = 900.0) -> None:
        ready = self._wait_line(lambda ln: ln.startswith("serened ready:"),
                                timeout_s, "ready")
        for line in self._lines():
            if "compile_cache=" in line:
                self.cache_dir = line.rsplit("compile_cache=", 1)[1].strip()
                break
        # serened ready: pg=P http=H platform=X devices=N device_kind=K…
        body = ready.split(":", 1)[1].strip()
        head, _, kind = body.partition(" device_kind=")
        fields = dict(kv.split("=", 1) for kv in head.split())
        self.pg_port = int(fields["pg"])
        self.http_port = int(fields["http"])
        self.backend = {"platform": fields["platform"],
                        "kind": kind.strip(),
                        "count": int(fields["devices"])}

    @property
    def ports(self) -> dict:
        """The server's ports by the name a protocol module asks for."""
        return {"pg": self.pg_port, "http": self.http_port}

    def _lines(self) -> list[str]:
        """The child's complete output lines so far (each poll reads only
        what was appended since the last)."""
        with open(self.log_path, "rb") as f:
            f.seek(self._read_to)
            chunk = f.read()
        end = chunk.rfind(b"\n") + 1
        self._read_to += end
        self._log_lines += chunk[:end].decode(errors="replace").splitlines(
            keepends=True)
        return self._log_lines

    def _wait_line(self, pred, timeout_s: float, what: str) -> str:
        deadline = time.monotonic() + timeout_s
        while True:
            for line in self._lines():
                if pred(line):
                    return line.strip()
            if self.proc.poll() is not None:
                raise ServerError(
                    f"serened exited with {self.proc.returncode} while the "
                    f"benchmark waited for {what}:\n{self.log_tail()}")
            if time.monotonic() > deadline:
                raise ServerError(f"no {what} from serened after "
                                  f"{timeout_s:.0f}s:\n{self.log_tail()}")
            time.sleep(0.05)

    def control(self, cmd: str, timeout_s: float = 300.0) -> dict:
        """One command to the child's control thread, and its reply."""
        self._tag += 1
        tag = f"c{self._tag}"
        self.proc.stdin.write(f"{tag} {cmd}\n")
        self.proc.stdin.flush()
        prefix = "BENCHCTL "

        def mine(line):
            return line.startswith(prefix) and \
                json.loads(line[len(prefix):]).get("tag") == tag

        rep = json.loads(self._wait_line(mine, timeout_s,
                                         f"reply to {cmd!r}")[len(prefix):])
        if not rep.get("ok"):
            raise ServerError(f"control {cmd!r} failed: {rep.get('error')}")
        return rep

    def log_tail(self, n: int = 30) -> str:
        try:
            return "".join(self._lines()[-n:])
        except OSError:
            return ""

    def stop(self, timeout_s: float = 120.0) -> None:
        """SIGTERM and wait; a server that does not exit cleanly is an
        error (it is killed so that nothing is left behind)."""
        if self.proc is None:
            return
        if self.proc.poll() is not None:
            raise ServerError(f"serened was not running at stop "
                              f"(rc={self.proc.returncode}):\n"
                              f"{self.log_tail()}")
        self.proc.send_signal(signal.SIGTERM)
        try:
            rc = self.proc.wait(timeout=timeout_s)
        except subprocess.TimeoutExpired:
            self.kill()
            raise ServerError(f"serened did not exit within {timeout_s:.0f}s "
                              f"of SIGTERM:\n{self.log_tail()}")
        finally:
            self._close_pipes()
        if rc != 0:
            raise ServerError(f"serened exited {rc} on SIGTERM:\n"
                              f"{self.log_tail()}")

    def _close_pipes(self):
        try:
            if self.proc.stdin:
                self.proc.stdin.close()
        except OSError:
            pass
        self._log.close()

    def kill(self) -> None:
        if self.proc is not None and self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait(timeout=30)
            self._close_pipes()
