"""Subprocess launcher for engine replica workers.

Spawns N copies of ``python -m repro.serving.cluster.worker``, each with
its own environment: on the CPU, ``XLA_FLAGS
--xla_force_host_platform_device_count`` is set *per child* (replacing
any inherited forced count) so every replica owns its own mesh slice; on
a TPU host, each replica is pinned to its own chip, and a cluster of more
replicas than chips is refused.  The parent router process never imports
jax and is unaffected.  Workers dial back to the router's
listening socket; ``accept_workers`` pairs each accepted connection with
its ``ready`` message so the router gets handles in replica order no
matter the connect order.

Teardown discipline (the CI cluster job SIGTERMs the router and asserts
no orphans): ``stop()`` broadcasts ``shutdown`` on any still-open
transports, waits ``grace`` seconds for voluntary exit, then escalates
terminate -> kill.  ``WorkerProcesses`` is a context manager and its
``__exit__`` always reaps, so an exception between spawn and accept
cannot leak children.

No jax in this module (subprocess/socket plumbing only) — the children
are the ones that pay device-runtime startup.
"""
from __future__ import annotations

import glob
import os
import socket
import subprocess
import sys
from typing import Optional

from repro.serving.cluster.protocol import (ClusterError, MessageStream,
                                            ProtocolError)


def worker_command(*, connect: str, replica_id: int, arch: str,
                   smoke: bool = False, slots: int = 4, max_len: int = 256,
                   block_size: int = 16, num_blocks: Optional[int] = None,
                   prefill_chunk: int = 64, share_prefix: bool = False,
                   metrics_window: float = 10.0) -> list[str]:
    cmd = [sys.executable, "-m", "repro.serving.cluster.worker",
           "--connect", connect, "--replica-id", str(replica_id),
           "--arch", arch, "--slots", str(slots),
           "--max-len", str(max_len), "--block-size", str(block_size),
           "--prefill-chunk", str(prefill_chunk),
           "--metrics-window", str(metrics_window)]
    if smoke:
        cmd.append("--smoke")
    if num_blocks is not None:
        cmd += ["--num-blocks", str(num_blocks)]
    if share_prefix:
        cmd.append("--share-prefix")
    return cmd


_GOOGLE_PCI_VENDOR = "0x1ae0"
# PCI device ids of TPU chips (v4, v5p, v5e, v6e)
_TPU_PCI_DEVICES = {"0x005e", "0x0062", "0x0063", "0x006f"}
TPU_PROCESS_PORT_BASE = 8476     # replica i's slice builder listens on +i


def tpu_chip_count(pci_root: str = "/sys/bus/pci/devices") -> int:
    """TPU chips attached to this host, read from PCI ids: no jax, no
    libtpu, so the router process never holds a chip."""
    n = 0
    for vendor in glob.glob(os.path.join(pci_root, "*", "vendor")):
        try:
            with open(vendor) as f:
                if f.read().strip() != _GOOGLE_PCI_VENDOR:
                    continue
            with open(os.path.join(os.path.dirname(vendor), "device")) as f:
                n += f.read().strip() in _TPU_PCI_DEVICES
        except OSError:
            continue
    return n


def tpu_chips_for_workers(env=None) -> int:
    """Chips the workers would open: 0 off a TPU host, or when
    ``JAX_PLATFORMS`` keeps jax off the TPU."""
    platforms = (os.environ if env is None else env).get("JAX_PLATFORMS")
    if platforms and "tpu" not in platforms.split(","):
        return 0
    return tpu_chip_count()


def worker_env(replica_id: int = 0, devices_per_worker: int = 1) -> dict:
    """Child environment with the per-worker device slice applied.

    On a TPU host, replica ``i`` sees chip ``i`` only, as a one-chip slice
    with its own slice-builder port, so replicas never open each other's
    chips.  Elsewhere, any inherited forced host-device count is
    *replaced*, not appended — XLA honors the last occurrence, but a stale
    flag would make the intent unreadable in ps output."""
    env = dict(os.environ)
    chips = tpu_chips_for_workers(env)
    if chips:
        if devices_per_worker != 1:
            raise ClusterError(f"on a TPU host each replica holds one "
                               f"chip; devices_per_worker="
                               f"{devices_per_worker}")
        if not 0 <= replica_id < chips:
            raise ClusterError(f"replica {replica_id} has no chip: this "
                               f"host has {chips}")
        env.update(TPU_VISIBLE_CHIPS=str(replica_id),
                   TPU_CHIPS_PER_PROCESS_BOUNDS="1,1,1",
                   TPU_PROCESS_BOUNDS="1,1,1",
                   TPU_PROCESS_PORT=str(TPU_PROCESS_PORT_BASE + replica_id))
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if not f.startswith("--xla_force_host_platform_device_count")]
    if devices_per_worker > 1:
        flags.append(f"--xla_force_host_platform_device_count="
                     f"{devices_per_worker}")
    env["XLA_FLAGS"] = " ".join(flags)
    if not env["XLA_FLAGS"]:
        del env["XLA_FLAGS"]
    return env


class WorkerProcesses:
    """Owns the worker subprocesses of one cluster."""

    def __init__(self, procs: list[subprocess.Popen]):
        self.procs = procs

    @classmethod
    def spawn(cls, n_replicas: int, *, connect: str, arch: str,
              devices_per_worker: int = 1,
              **worker_kwargs) -> "WorkerProcesses":
        chips = tpu_chips_for_workers()
        if chips and n_replicas > chips:
            raise ClusterError(f"{n_replicas} replicas need one chip each; "
                               f"this host has {chips}")
        procs = []
        try:
            for i in range(n_replicas):
                cmd = worker_command(connect=connect, replica_id=i,
                                     arch=arch, **worker_kwargs)
                procs.append(subprocess.Popen(
                    cmd, env=worker_env(i, devices_per_worker)))
        except Exception:
            cls(procs).stop(grace=2.0)
            raise
        return cls(procs)

    @property
    def pids(self) -> list[int]:
        return [p.pid for p in self.procs]

    def poll_dead(self) -> list[int]:
        """Indices of workers whose process has exited."""
        return [i for i, p in enumerate(self.procs) if p.poll() is not None]

    def stop(self, *, streams: Optional[list] = None,
             grace: float = 5.0) -> list[int]:
        """Reap every worker: polite shutdown message (when transports are
        provided), then wait, then terminate, then kill.  Returns exit
        codes.  Never raises — teardown must always finish."""
        if streams:
            for s in streams:
                try:
                    s.send({"type": "shutdown"})
                except Exception:
                    pass
        for p in self.procs:
            try:
                p.wait(timeout=grace)
            except subprocess.TimeoutExpired:
                p.terminate()
        for p in self.procs:
            try:
                p.wait(timeout=grace)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()
        return [p.returncode for p in self.procs]

    def __enter__(self) -> "WorkerProcesses":
        return self

    def __exit__(self, *exc) -> None:
        self.stop()


def listen_socket(host: str = "127.0.0.1", port: int = 0) -> socket.socket:
    """Router-side listening socket (port 0 = ephemeral; read the bound
    port off ``.getsockname()``)."""
    srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    srv.bind((host, port))
    srv.listen(16)
    return srv


def accept_workers(srv: socket.socket, n: int, *, timeout: float = 120.0,
                   procs: Optional[WorkerProcesses] = None) \
        -> dict[int, tuple[MessageStream, dict]]:
    """Accept ``n`` worker connections and pair each with its ``ready``
    message -> {replica_id: (stream, ready_msg)}.  The generous default
    timeout covers first-run jit compilation in the children.  Raises
    ClusterError if a worker process dies before connecting (checked
    between accepts via ``procs``) or the timeout lapses."""
    srv.settimeout(1.0)
    deadline = timeout
    by_replica: dict[int, tuple[MessageStream, dict]] = {}
    while len(by_replica) < n:
        if procs is not None and procs.poll_dead():
            raise ClusterError(f"worker(s) {procs.poll_dead()} exited "
                               f"before connecting")
        try:
            conn, _ = srv.accept()
        except socket.timeout:
            deadline -= 1.0
            if deadline <= 0:
                raise ClusterError(
                    f"timed out waiting for workers "
                    f"({len(by_replica)}/{n} connected)") from None
            continue
        conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        stream = MessageStream(conn)
        ready = _wait_ready(stream)
        rid = int(ready["replica"])
        if rid in by_replica:
            raise ProtocolError(f"two workers claimed replica id {rid}")
        by_replica[rid] = (stream, ready)
    return by_replica


def _wait_ready(stream: MessageStream, timeout: float = 30.0) -> dict:
    waited = 0.0
    while waited < timeout:
        msgs = stream.poll(0.5)
        if msgs:
            if msgs[0].get("type") != "ready":
                raise ProtocolError(f"worker's first message was "
                                    f"{msgs[0].get('type')!r}, not ready")
            return msgs[0]
        waited += 0.5
    raise ClusterError("worker connected but never sent ready")
