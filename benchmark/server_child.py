"""The parameter-server role, started the way a deployment starts it:
``python -m byteps_tpu.server`` with the ``DMLC_*`` environment, pinned
to the CPU by its environment (one process holds the chip), and — where
the traffic file says so — to its own set of cores, as the launcher does
for ranks.
"""

from __future__ import annotations

import os
import socket
import subprocess
import sys
import tempfile
import time
from typing import Optional, Sequence

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def split_cores(placement: Optional[dict]):
    """(worker cores, server cores) from the traffic file's
    ``placement``: ``{"server_cores": n}`` gives the server child the
    last ``n`` cores this process may run on and the worker the rest.
    ``None`` pins nothing."""
    if not placement:
        return None, None
    avail = sorted(os.sched_getaffinity(0))
    n = int(placement["server_cores"])
    if not 0 < n < len(avail):
        raise ValueError(
            f"placement asks {n} server cores of {len(avail)} available")
    return avail[:-n], avail[-n:]


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class ServerChild:
    def __init__(self, port: int, cores: Optional[Sequence[int]] = None):
        self.port = port
        self._log = tempfile.TemporaryFile(mode="w+")
        env = {**os.environ,
               "DMLC_ROLE": "server", "DMLC_NUM_WORKER": "1",
               "DMLC_NUM_SERVER": "1", "DMLC_PS_ROOT_URI": "127.0.0.1",
               "DMLC_PS_ROOT_PORT": str(port), "JAX_PLATFORMS": "cpu",
               "PYTHONPATH": REPO + os.pathsep
               + os.environ.get("PYTHONPATH", "")}
        pin = (lambda: os.sched_setaffinity(0, cores)) if cores else None
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "byteps_tpu.server"], cwd=REPO, env=env,
            stdout=self._log, stderr=subprocess.STDOUT, preexec_fn=pin)

    def wait_listening(self, timeout_s: float = 60.0) -> None:
        deadline = time.monotonic() + timeout_s
        while True:
            if self.proc.poll() is not None:
                raise RuntimeError(
                    f"server exited rc={self.proc.returncode}:\n{self.tail()}")
            try:
                with socket.create_connection(("127.0.0.1", self.port),
                                              timeout=1):
                    return
            except OSError:
                if time.monotonic() > deadline:
                    raise RuntimeError(
                        f"server on :{self.port} never came up:\n"
                        f"{self.tail()}")
                time.sleep(0.05)

    def tail(self, n: int = 4000) -> str:
        self._log.flush()
        self._log.seek(0)
        return self._log.read()[-n:]

    def wait_exit(self, timeout_s: float = 30.0) -> int:
        """The server exits 0 by itself once its worker sent SHUTDOWN."""
        return self.proc.wait(timeout=timeout_s)

    def kill(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait(timeout=10)
        self._log.close()
