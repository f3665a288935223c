"""The one child process: the program's CLI server (the pattern of
``chip_smoke.py``, copied and not imported). Stdlib only; the parent never
touches a device."""

from __future__ import annotations

import json
import os
import re
import shutil
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
LOG_DIR = HERE / ".cache" / "logs"
FLIGHT_DIR = HERE / ".cache" / "flight"     # the SIGTERM drain's dump
PROFILE_ROOT = HERE / ".cache" / "profile"  # /debug/profile, one dir a run
HEALTH_TIMEOUT_S = 900      # a cold start builds weights for minutes
DRAIN_TIMEOUT_S = 60


# The server's first log line names the device, before any weight is built:
# a start on the wrong platform is stopped within seconds, not minutes.
DEVICE_LINE = re.compile(r"device: platform=(\S+) device_kind=(.+?) "
                         r"device_count=(\d+)")


class ServerFailure(Exception):
    pass


class Server:
    def __init__(self, config: dict, tokenizer_dir: Path, tag: str,
                 cpu_rehearsal: bool = False):
        LOG_DIR.mkdir(parents=True, exist_ok=True)
        self.log_path = LOG_DIR / f"server-{tag}.log"
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            self.port = s.getsockname()[1]
        self.base = f"http://127.0.0.1:{self.port}"
        # This run's own profile directory, emptied first: whatever lies
        # there after a capture is this run's and nobody else's.
        self.profile_dir = PROFILE_ROOT / tag
        shutil.rmtree(self.profile_dir, ignore_errors=True)
        self.profile_dir.mkdir(parents=True)
        FLIGHT_DIR.mkdir(parents=True, exist_ok=True)
        # serve.py is api_server.main() with the profiler's output
        # directory redirected into the checkout (see its docstring).
        cmd = [sys.executable, str(HERE / "serve.py"),
               "--model", config["preset"], "--host", "127.0.0.1",
               "--port", str(self.port), "--tokenizer", str(tokenizer_dir)]
        cmd += [str(f) for f in config["server_flags"]]
        # The environment passes through: the compile cache goes where
        # JAX_COMPILATION_CACHE_DIR says, else to the program's default,
        # <checkout>/.jax_compile_cache (a fixed path inside the checkout).
        env = dict(os.environ)
        env["TOKENIZERS_PARALLELISM"] = "false"
        # Everything the server writes stays inside the checkout (or under
        # HOME / TMPDIR, which the driver gives each side separately): the
        # program's defaults are the fixed /tmp/kgct-flight and
        # /tmp/kgct-profile.
        env["KGCT_FLIGHT_DIR"] = str(FLIGHT_DIR)
        env["PERFBENCH_PROFILE_DIR"] = str(self.profile_dir)
        if cpu_rehearsal:
            env["JAX_PLATFORMS"] = "cpu"
        self.t_start = time.monotonic()
        with open(self.log_path, "wb") as log:
            self.proc = subprocess.Popen(
                cmd, cwd=ROOT, env=env, stdout=log, stderr=subprocess.STDOUT,
                start_new_session=True)

    def log_tail(self, n: int = 30) -> str:
        try:
            lines = self.log_path.read_text(errors="replace").splitlines()
        except OSError:
            return ""
        return "\n".join(lines[-n:])

    def get(self, path: str, timeout: float = 10):
        """(status, body text). Connection refused -> (None, '')."""
        try:
            with urllib.request.urlopen(self.base + path,
                                        timeout=timeout) as r:
                return r.status, r.read().decode()
        except urllib.error.HTTPError as e:
            return e.code, e.read().decode()
        except (urllib.error.URLError, ConnectionError, socket.timeout):
            return None, ""

    def wait_healthy(self, want_platform: str, want_chips: int) -> dict:
        t_end = time.monotonic() + HEALTH_TIMEOUT_S
        device_seen = False
        while time.monotonic() < t_end:
            rc = self.proc.poll()
            if rc is not None:
                raise ServerFailure(
                    f"server exited with code {rc} before /health; log "
                    f"tail ({self.log_path}):\n{self.log_tail()}")
            if not device_seen:
                m = DEVICE_LINE.search(
                    self.log_path.read_text(errors="replace"))
                if m:
                    device_seen = True
                    if m.group(1) != want_platform:
                        raise ServerFailure(
                            f"the server runs on {m.group(1)!r}, not "
                            f"{want_platform!r}: the benchmark needs the "
                            "accelerator")
                    if int(m.group(3)) < want_chips:
                        raise ServerFailure(
                            f"{m.group(3)} chip(s), the cell needs "
                            f"{want_chips}")
            status, text = self.get("/health", timeout=5)
            if status == 200:
                return json.loads(text)
            time.sleep(0.25)
        raise ServerFailure(f"no /health 200 within {HEALTH_TIMEOUT_S}s; "
                            f"log tail:\n{self.log_tail()}")

    def stop(self) -> int:
        """SIGTERM, wait for the drain, return the exit code; SIGKILL the
        whole group if it does not go."""
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(DRAIN_TIMEOUT_S)
            except subprocess.TimeoutExpired:
                self.kill()
        return self.proc.returncode

    def kill(self) -> None:
        if self.proc.poll() is None:
            try:
                os.killpg(self.proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            self.proc.wait()
