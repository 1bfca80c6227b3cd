"""The server process (chip_smoke.py's `Server`, copied) and what the
benchmark reads from it: /healthz, /metrics, the launcher's files."""

import http.client
import json
import os
import socket
import subprocess
import sys
import time

from .fixture import REQUEST_TIMEOUT_S

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH_DIR)
COOKIE = {"Cookie": "sessionid=benchmark"}


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def compile_cache_dir() -> str:
    """Where the server keeps its compile cache: the variable if the
    machine sets it, else <checkout>/.jax_cache (runtime/jax_cache.py's
    own default on a TPU): a fixed path inside the checkout."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        REPO, ".jax_cache"
    )


def cache_entries() -> int:
    path = compile_cache_dir()
    return len(os.listdir(path)) if os.path.isdir(path) else 0


def parse_metrics(text: str) -> dict:
    """Prometheus text -> {'name{labels}': value}."""
    out = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        key, _, value = line.rpartition(" ")
        try:
            out[key] = float(value)
        except ValueError:
            continue
    return out


class Server:
    """One child process: the launcher around the real `server.main`."""

    def __init__(self, workdir: str, argv: list, port: int, name="sut",
                 command=None):
        self.port = port
        self.workdir = workdir
        self.log_path = os.path.join(workdir, f"server-{name}.log")
        self._log = open(self.log_path, "w")
        env = dict(os.environ)
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        env.pop("BENCH_RUN", None)
        command = command or [
            sys.executable, os.path.join(HERE, "launcher.py"), workdir, "--",
        ]
        self.proc = subprocess.Popen(
            command + argv, cwd=REPO, env=env,
            stdout=self._log, stderr=subprocess.STDOUT,
        )

    def get(self, path: str, timeout: float = REQUEST_TIMEOUT_S):
        conn = http.client.HTTPConnection(
            "127.0.0.1", self.port, timeout=timeout
        )
        try:
            conn.request("GET", path, headers=COOKIE)
            resp = conn.getresponse()
            return resp.status, dict(resp.getheaders()), resp.read()
        finally:
            conn.close()

    def healthz(self) -> dict:
        status, _, body = self.get("/healthz", timeout=30.0)
        if status != 200:
            raise RuntimeError(f"/healthz answered {status}")
        return json.loads(body)

    def metrics(self) -> dict:
        status, _, body = self.get("/metrics", timeout=30.0)
        if status != 200:
            raise RuntimeError(f"/metrics answered {status}")
        return parse_metrics(body.decode())

    def counters(self) -> dict:
        """One reading of everything the layer metrics take deltas of."""
        return {
            "healthz": self.healthz(),
            "metrics": self.metrics(),
            "cache_entries": cache_entries(),
        }

    def wait_healthy(self, limit_s: float = 600.0) -> float:
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < limit_s:
            if self.proc.poll() is not None:
                raise RuntimeError(
                    f"server exited rc={self.proc.returncode} before "
                    f"/healthz answered:\n{self.log_tail()}"
                )
            try:
                self.healthz()
                return time.perf_counter() - t0
            except (OSError, http.client.HTTPException):
                time.sleep(0.25)
        raise RuntimeError(
            f"server not healthy after {limit_s:.0f}s:\n{self.log_tail()}"
        )

    # -- the launcher's files ------------------------------------------

    def ask(self, request: str, answer: str, limit_s: float = 120.0) -> dict:
        """Drop `request` into the work directory and wait for the
        launcher to write `answer` (a JSON file)."""
        answer_path = os.path.join(self.workdir, answer)
        if os.path.exists(answer_path):
            os.remove(answer_path)
        open(os.path.join(self.workdir, request), "w").close()
        error_path = os.path.join(self.workdir, "launcher.error")
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < limit_s:
            if os.path.exists(answer_path):
                with open(answer_path) as f:
                    return json.load(f)
            if os.path.exists(error_path):
                with open(error_path) as f:
                    raise RuntimeError(f"launcher: {json.load(f)['error']}")
            if self.proc.poll() is not None:
                raise RuntimeError("server exited while the launcher was asked")
            time.sleep(0.02)
        raise RuntimeError(f"launcher did not answer {request} in {limit_s}s")

    def log_text(self) -> str:
        self._log.flush()
        with open(self.log_path, errors="replace") as f:
            return f.read()

    def log_tail(self, lines: int = 60) -> str:
        return "\n".join(self.log_text().splitlines()[-lines:])

    def stop(self) -> None:
        if self.proc.poll() is None:
            self.proc.terminate()
            try:
                self.proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        if not self._log.closed:
            self._log.close()
