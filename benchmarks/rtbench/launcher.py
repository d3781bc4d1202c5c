"""The server child: ``python -m spark_rapids_tpu.server --port 0`` exactly
as a deployment starts it, or, for a traced run, ``serve_traced.py``, which
calls the same ``main()`` with a profiler beside it. The launcher and the
device check are chip_smoke.py's."""

import os
import subprocess
import sys
import threading

from . import loader

READY_PREFIX = "spark-rapids-tpu plan server listening on "


class Server:
    def __init__(self, traced_control_dir=None):
        if traced_control_dir is None:
            cmd = [sys.executable, "-m", "spark_rapids_tpu.server",
                   "--port", "0"]
        else:
            cmd = [sys.executable,
                   os.path.join(loader.ROOT, "serve_traced.py"),
                   "--control", traced_control_dir, "--port", "0"]
        self.proc = subprocess.Popen(cmd, cwd=loader.REPO,
                                     stdout=subprocess.PIPE, text=True)
        self.port = None
        self._ready = threading.Event()
        self._pump = threading.Thread(target=self._read, daemon=True)
        self._pump.start()

    def _read(self):
        for line in self.proc.stdout:
            if self.port is None and line.startswith(READY_PREFIX):
                self.port = int(line.rsplit(":", 1)[1])
                self._ready.set()
            else:
                print(f"[server] {line.rstrip()}", file=sys.stderr)
        self._ready.set()

    def wait_ready(self, timeout_s):
        self._ready.wait(timeout_s)
        if self.port is None:
            code = self.proc.poll()
            self.kill()
            raise loader.BenchmarkError(
                f"the server gave no readiness line within {timeout_s} s "
                f"(exit code {code}): no device, or it failed to start")
        return self.port

    def shutdown(self, client, timeout_s=60):
        """Stop through the ``shutdown`` op and wait; returns the exit
        code."""
        try:
            client._request({"msg": "shutdown"})
        except (OSError, RuntimeError):
            pass        # the ack may lose the race with the server's close
        client.close()
        try:
            return self.proc.wait(timeout=timeout_s)
        except subprocess.TimeoutExpired:
            self.kill()
            return None

    def kill(self):
        if self.proc.poll() is None:
            self.proc.kill()
        self.proc.wait()
        self._pump.join(timeout=5)
