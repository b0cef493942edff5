"""The worker pool: real OS processes, spawned once, reused forever.

Spawning a worker costs a full interpreter + jax import (seconds on a
contended host), so the process transport never pays it per run: one
process-global pool spawns workers lazily, leases ``n`` of them to each
``run_ranks`` call, and takes them back afterwards.  Only a worker that
actually DIED (a SIGKILL fault cell, a crash) is replaced — the
respawn-only-after-a-kill discipline is what keeps a tier-1 suite full
of process-backend tests inside its wall-clock budget, and it is
regression-tested by PID stability across runs.

Rendezvous is an ``AF_UNIX`` listener in a private temp directory: each
worker connects back and introduces itself with a ``hello`` frame
carrying its PID (accept order is arbitrary — the PID is how a socket
is matched to its ``Popen``).  Workers inherit the parent environment
with ``JAX_PLATFORMS`` pinned to ``cpu`` and the repo root on
``PYTHONPATH``; both ends are the same interpreter on the same
checkout, which is what lets the wire stay plain pickle (wire.py).
"""

from __future__ import annotations

import atexit
import os
import shutil
import socket
import subprocess
import sys
import tempfile
import threading
from typing import Dict, List, Optional

from .wire import recv_frame, send_frame

__all__ = ["Worker", "WorkerPool", "shared_pool", "shutdown_shared_pool"]

# Generous: a cold worker pays the full package import serially on a
# contended single-core host; 8 workers can take minutes end to end.
_SPAWN_TIMEOUT_S = float(os.environ.get(
    "MPI4TORCH_TPU_TRANSPORT_SPAWN_TIMEOUT", "300"))


class Worker:
    """One pooled worker process and its parent-side socket."""

    __slots__ = ("proc", "sock", "pid", "wlock", "alive")

    def __init__(self, proc: subprocess.Popen, sock: socket.socket,
                 pid: int):
        self.proc = proc
        self.sock = sock
        self.pid = pid
        # Serializes parent-side frame writes: switchboard replies come
        # from reader, completion, and janitor threads.
        self.wlock = threading.Lock()
        self.alive = True

    def send(self, frame: dict) -> None:
        send_frame(self.sock, frame, lock=self.wlock)

    def mark_dead(self) -> None:
        self.alive = False

    def is_live(self) -> bool:
        return self.alive and self.proc.poll() is None


class WorkerPool:
    """Lazily-grown, reused-by-default pool of transport workers."""

    def __init__(self):
        self._tmpdir = tempfile.mkdtemp(prefix="m4t_transport_")
        self.addr = os.path.join(self._tmpdir, "sock")
        self._listener = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        self._listener.bind(self.addr)
        self._listener.listen(64)
        self._workers: List[Worker] = []
        self._lock = threading.Lock()
        self._closed = False
        # How many workers this pool ever spawned — the reuse
        # regression's counter: two back-to-back healthy runs must not
        # advance it.
        self.spawned_total = 0

    # ------------------------------------------------------------ spawn

    def _spawn_env(self) -> Dict[str, str]:
        env = dict(os.environ)
        # Workers are the Mode B host-side runtime: eager jax on the
        # CPU, always.  An accelerator belongs to one process at a
        # time, so a worker inheriting JAX_PLATFORMS=tpu would ask for
        # a chip its parent holds and fail or hang.
        env["JAX_PLATFORMS"] = "cpu"
        repo_root = os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
        env["PYTHONPATH"] = repo_root + os.pathsep + env.get(
            "PYTHONPATH", "")
        if "jax" in sys.modules:
            # Replicate the parent's x64 mode even when it was enabled
            # via jax.config rather than the environment (bit parity:
            # default dtypes decide the bits a rank body computes).
            import jax
            env["JAX_ENABLE_X64"] = \
                "1" if jax.config.jax_enable_x64 else "0"
        return env

    def _spawn(self, n: int) -> List[Worker]:
        env = self._spawn_env()
        procs = [subprocess.Popen(
            [sys.executable, "-m", "mpi4torch_tpu.transport._worker",
             self.addr], env=env) for _ in range(n)]
        by_pid: Dict[int, subprocess.Popen] = {p.pid: p for p in procs}
        out: List[Worker] = []
        self._listener.settimeout(_SPAWN_TIMEOUT_S)
        try:
            while by_pid:
                try:
                    sock, _ = self._listener.accept()
                except socket.timeout:
                    raise TimeoutError(
                        f"transport worker spawn timed out after "
                        f"{_SPAWN_TIMEOUT_S}s waiting for "
                        f"{len(by_pid)} worker(s) to connect")
                hello = recv_frame(sock)
                if not hello or hello.get("kind") != "hello":
                    sock.close()
                    continue
                pid = hello["pid"]
                proc = by_pid.pop(pid, None)
                if proc is None:
                    # A connect-back from a worker this spawn batch does
                    # not own (stale retry) — refuse it.
                    sock.close()
                    continue
                out.append(Worker(proc, sock, pid))
                self.spawned_total += 1
        except BaseException:
            for w in out:
                w.sock.close()
            for p in procs:
                p.kill()
            raise
        return out

    # ------------------------------------------------------------ lease

    def lease(self, n: int) -> List[Worker]:
        """Hand out ``n`` live workers, spawning only the deficit."""
        with self._lock:
            if self._closed:
                raise RuntimeError("worker pool is shut down")
            self._prune_dead()
            deficit = n - len(self._workers)
            if deficit > 0:
                self._workers.extend(self._spawn(deficit))
            return self._workers[:n]

    def _prune_dead(self) -> None:
        live = []
        for w in self._workers:
            if w.is_live():
                live.append(w)
            else:
                try:
                    w.sock.close()
                except OSError:
                    pass
                w.proc.poll() or w.proc.kill()
                w.proc.wait()
        self._workers = live

    def release(self, workers: List[Worker]) -> None:
        """Return leased workers; dead ones are reaped, live ones kept."""
        with self._lock:
            self._prune_dead()

    def pids(self) -> List[int]:
        with self._lock:
            return [w.pid for w in self._workers if w.is_live()]

    # --------------------------------------------------------- shutdown

    def shutdown(self) -> None:
        with self._lock:
            if self._closed:
                return
            self._closed = True
            for w in self._workers:
                if w.is_live():
                    try:
                        w.send({"kind": "shutdown"})
                    except OSError:
                        pass
                try:
                    w.sock.close()
                except OSError:
                    pass
            for w in self._workers:
                try:
                    w.proc.wait(timeout=5)
                except subprocess.TimeoutExpired:
                    w.proc.kill()
                    w.proc.wait()
            self._workers = []
            try:
                self._listener.close()
            finally:
                shutil.rmtree(self._tmpdir, ignore_errors=True)


_shared: Optional[WorkerPool] = None
_shared_lock = threading.Lock()


def shared_pool() -> WorkerPool:
    """The process-global pool (created on first use, reaped atexit)."""
    global _shared
    with _shared_lock:
        if _shared is None or _shared._closed:
            _shared = WorkerPool()
            atexit.register(_shared.shutdown)
        return _shared


def shutdown_shared_pool() -> None:
    global _shared
    with _shared_lock:
        pool, _shared = _shared, None
    if pool is not None:
        pool.shutdown()
