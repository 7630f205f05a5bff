"""Head (GCS) restart with live daemon reconnection.

The reference's control-plane fault-tolerance story: the GCS process dies
and restarts against its persistent tables, and live raylets RE-REGISTER
instead of dying with it (gcs_redis_failure_detector.h; raylet notify path
core_worker.h:1105). Here: a head process is SIGKILLed mid-session, the
node daemon survives (reconnect-with-backoff window), a restarted head on
the same port+token restores the GCS snapshot, the daemon re-registers,
the restored detached actor schedules back onto it, and fresh tasks run —
all without the daemon process restarting.
"""

from __future__ import annotations

import os
import socket
import subprocess
import sys
import threading
import time

import pytest

SCRIPT = os.path.join(os.path.dirname(__file__), "_head_script.py")
TOKEN = "restarttok"


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


class _LineReader:
    """Background reader so subprocess stdout never blocks the pipe."""

    def __init__(self, proc: subprocess.Popen):
        self.lines: list[str] = []
        self._cond = threading.Condition()
        self._proc = proc
        threading.Thread(target=self._pump, daemon=True).start()

    def _pump(self) -> None:
        for line in self._proc.stdout:
            with self._cond:
                self.lines.append(line.rstrip("\n"))
                self._cond.notify_all()

    def wait_for(self, prefix: str, timeout: float) -> str:
        deadline = time.monotonic() + timeout
        with self._cond:
            while True:
                for line in self.lines:
                    if line.startswith(prefix):
                        return line
                left = deadline - time.monotonic()
                if left <= 0:
                    raise TimeoutError(
                        f"no {prefix!r} from subprocess; got {self.lines!r}"
                    )
                self._cond.wait(timeout=min(left, 0.5))


def _spawn_head(phase: str, port: int, gcs: str) -> tuple:
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.Popen(
        [
            sys.executable,
            SCRIPT,
            "--phase",
            phase,
            "--port",
            str(port),
            "--gcs",
            gcs,
            "--token",
            TOKEN,
        ],
        env=env,
        stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT,
        text=True,
    )
    return proc, _LineReader(proc)


@pytest.mark.slow
def test_head_restart_daemon_reconnects(tmp_path):
    port = _free_port()
    gcs = str(tmp_path / "gcs.snap")
    head1 = head2 = daemon = None
    try:
        head1, head1_out = _spawn_head("first", port, gcs)
        daemon = subprocess.Popen(
            [
                sys.executable,
                "-m",
                "ray_tpu._private.node_daemon",
                "--address",
                f"127.0.0.1:{port}?token={TOKEN}",
                "--num-cpus",
                "4",
                "--resources",
                '{"dnode": 1}',
                "--reconnect-window",
                "90",
            ],
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
        )
        daemon_out = _LineReader(daemon)

        actor_line = head1_out.wait_for("ACTOR_PID", timeout=120)
        old_actor_pid = int(actor_line.split()[1])
        head1_out.wait_for("READY", timeout=30)

        # Control-plane CRASH: no shutdown frames reach the daemon.
        head1.kill()
        head1.wait(timeout=10)
        time.sleep(2.0)
        assert daemon.poll() is None, "daemon died with the head (fate-shared)"

        head2, head2_out = _spawn_head("second", port, gcs)
        survivor = head2_out.wait_for("SURVIVOR", timeout=120)
        _, state, new_actor_pid = survivor.split()
        assert state == "alive"
        # Fresh worker process for the restored actor (state is rebuilt, the
        # reference's restart semantics), hosted by the SAME daemon.
        task_line = head2_out.wait_for("TASKPPID", timeout=60)
        assert int(task_line.split()[1]) == daemon.pid, (
            "task did not run under the original daemon process"
        )
        head2_out.wait_for("DONE", timeout=60)
        assert daemon.poll() is None, "daemon restarted during head recovery"
        assert int(new_actor_pid) != old_actor_pid  # old worker was orphaned
        assert head2.wait(timeout=30) == 0
        # Clean head shutdown → explicit fate-sharing: daemon exits promptly.
        deadline = time.monotonic() + 15
        while daemon.poll() is None and time.monotonic() < deadline:
            time.sleep(0.2)
        assert daemon.poll() is not None, "daemon ignored clean head shutdown"
    finally:
        for proc in (head1, head2, daemon):
            if proc is not None and proc.poll() is None:
                proc.kill()
                proc.wait(timeout=10)
