"""Engine replica worker: one ``ContinuousBatchingEngine`` behind the
cluster wire protocol.

Run as a subprocess by the launcher (``python -m
repro.serving.cluster.worker --connect host:port --replica-id N ...``),
or driven in-process by tests (``EngineWorker`` over an
``InProcTransport`` — same message handling, no sockets, no forks).

The process model: each worker owns its own mesh slice via a per-process
``XLA_FLAGS --xla_force_host_platform_device_count`` (set by the
launcher, or by ``--devices`` here *before* jax is imported — which is
why every jax import in this module is deferred into functions).
Replicas are pure data-parallel and never communicate with each other;
``jax.distributed.initialize`` wiring exists behind ``--distributed``
for real multi-host meshes, and single-machine CI never takes that
branch, so no collectives are needed.

Parity contract: params come from ``engine.init_params`` with
``PRNGKey(0)`` — the same deterministic init on every replica — and
sampling keys are ``fold_in(seed, absolute_position)``, so a request
produces bit-identical tokens on ANY replica.  The CI cluster job asserts cluster outputs ==
single-process outputs token for token.

The pump loop is single-threaded and clock-free: it alternates between
draining the transport (poll timeout 0 while the engine has work, a
short idle wait otherwise) and stepping the engine; per-token ``token``
messages fire from the engine's ``on_token`` hook mid-step, ``finish``
messages flush from ``engine.completed`` after each step.  Heartbeats
need no timer here — any ``ping`` is answered on the next loop
iteration, and the router counts any message (tokens included) as proof
of life.
"""
from __future__ import annotations

import argparse
import os
import socket
import sys

from repro.serving.cluster.protocol import (ConnectionClosed, MessageStream,
                                            ProtocolError,
                                            sampling_from_wire)

IDLE_POLL_S = 0.05          # transport wait when the engine is idle


class EngineWorker:
    """Protocol adapter around one engine.  ``transport`` is anything
    with send/poll (MessageStream in the subprocess, InProcTransport in
    tests)."""

    def __init__(self, engine, transport, replica_id: int):
        self.engine = engine
        self.transport = transport
        self.replica = replica_id
        self._draining = False
        self._drained_sent = False
        self._shutdown = False
        self._n_flushed = 0              # engine.completed flush cursor
        prev = engine.on_token

        def tap(rid: int, tok: int) -> None:
            if prev is not None:
                prev(rid, tok)
            self.transport.send({"type": "token", "rid": rid, "token": tok})

        engine.on_token = tap

    # -- outbound ------------------------------------------------------
    def _flush_completed(self) -> None:
        done = self.engine.completed
        while self._n_flushed < len(done):
            o = done[self._n_flushed]
            self._n_flushed += 1
            self.transport.send({
                "type": "finish", "rid": o.request_id,
                "token_ids": list(o.token_ids),
                "finish_reason": o.finish_reason,
                "prompt_len": o.prompt_len, "ttft_s": o.ttft_s,
                "tpot_s": o.tpot_s, "logprobs": o.logprobs})

    def _stats(self) -> dict:
        from repro.serving.export import prometheus_text
        eng = self.engine
        return {
            "outstanding_tokens": eng.outstanding_tokens(),
            "in_flight": sum(s.busy for s in eng.slots),
            "queued": eng.scheduler.queue_depth,
            "completed": len(eng.completed),
            # lifetime counters, not windowed: the cluster bench sums these
            # across replicas for an exact aggregate hit rate
            "prefix_hits": eng.metrics.prefix_hit_tokens,
            "prefix_lookups": eng.metrics.prefix_lookup_tokens,
            "window": eng.metrics.window_signals(),
            "prom": prometheus_text(
                eng.metrics, labels={"replica": str(self.replica)}),
        }

    # -- inbound -------------------------------------------------------
    def _handle(self, m: dict) -> None:
        t = m.get("type")
        if t == "submit":
            self._handle_submit(m)
        elif t == "cancel":
            self.engine.cancel(int(m["rid"]),
                               reason=m.get("reason", "cancelled"))
        elif t == "ping":
            self.transport.send({"type": "pong", "seq": m.get("seq"),
                                 "stats": self._stats()})
        elif t == "stats":
            self.transport.send({"type": "stats", "stats": self._stats()})
        elif t == "drain":
            self._draining = True
        elif t == "shutdown":
            self._shutdown = True
        else:
            raise ProtocolError(f"unexpected message type {t!r} from router")

    def _handle_submit(self, m: dict) -> None:
        from repro.serving.engine import Request
        rid = int(m["rid"])
        if self._draining:
            self.transport.send({"type": "error", "rid": rid,
                                 "error": "draining",
                                 "message": "worker is draining"})
            return
        try:
            req = Request(id=rid,
                          prompt=[int(x) for x in m["prompt"]],
                          max_new_tokens=int(m["max_new_tokens"]),
                          priority=int(m.get("priority", 0)),
                          sampling=sampling_from_wire(m.get("sampling", {})))
            self.engine.submit(req)
        except (TypeError, ValueError) as e:
            # reject-at-submit surfaces as a typed error upstream; the rid
            # is finished-with-error, never silently dropped.  TypeError
            # matters as much as ValueError: wrong-typed wire JSON
            # ("temperature": null -> float(None)) must reject the one
            # request, never crash the replica process
            self.transport.send({"type": "error", "rid": rid,
                                 "error": "rejected", "message": str(e)})

    # -- loop ----------------------------------------------------------
    def pump(self, idle_poll: float = IDLE_POLL_S) -> bool:
        """One loop iteration: drain the transport, step the engine,
        flush finishes.  False once the worker should exit (shutdown
        message or router gone).  Tests drive this directly."""
        if self._shutdown:
            return False
        timeout = 0.0 if self.engine.has_work else idle_poll
        try:
            msgs = self.transport.poll(timeout)
        except ConnectionClosed:
            return False                 # router is gone: exit, don't orphan
        for m in msgs:
            self._handle(m)
        if self._shutdown:
            return False
        if self.engine.has_work:
            self.engine.step()
        try:
            self._flush_completed()
            if self._draining and not self.engine.has_work \
                    and not self._drained_sent:
                self._drained_sent = True
                self.transport.send({"type": "drained"})
        except ConnectionClosed:
            return False
        return True

    def serve_forever(self) -> None:
        while self.pump():
            pass


# ---------------------------------------------------------------------------
# subprocess entry point
# ---------------------------------------------------------------------------

def _apply_device_flags(devices: int) -> None:
    """Force the host-platform device count for THIS process.  Must run
    before jax is imported — which is why main() defers every jax import
    and the launcher prefers setting XLA_FLAGS in the child env."""
    if "jax" in sys.modules:
        raise RuntimeError("--devices must be applied before jax is "
                           "imported; launch the worker as a fresh process")
    flags = os.environ.get("XLA_FLAGS", "")
    os.environ["XLA_FLAGS"] = \
        f"{flags} --xla_force_host_platform_device_count={devices}".strip()


def build_engine(args):
    """Arch + params + mesh + engine for one replica (jax imports live
    here, after any XLA_FLAGS mutation)."""
    import jax

    from repro.configs import get_arch, reduce_for_smoke
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_host_mesh
    from repro.serving import (ContinuousBatchingEngine, ServingMetrics,
                               init_params)

    if args.distributed:
        # real multi-host wiring — never taken on single-machine CI, so
        # the data-parallel replicas there need no collective backend
        jax.distributed.initialize(
            coordinator_address=args.coordinator,
            num_processes=args.num_processes, process_id=args.process_id)
    enable_compile_cache()
    arch = get_arch(args.arch)
    if args.smoke:
        arch = reduce_for_smoke(arch)
    mesh = make_host_mesh()
    params = init_params(arch, mesh, jax.random.PRNGKey(0),   # same per replica
                         slots=args.slots, max_len=args.max_len)
    return ContinuousBatchingEngine(
        arch, params, mesh, slots=args.slots, max_len=args.max_len,
        block_size=args.block_size, num_blocks=args.num_blocks,
        prefill_chunk=args.prefill_chunk, share_prefix=args.share_prefix,
        metrics=ServingMetrics(window_s=args.metrics_window))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--connect", required=True,
                    help="router address host:port")
    ap.add_argument("--replica-id", type=int, required=True)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--num-blocks", type=int, default=None)
    ap.add_argument("--prefill-chunk", type=int, default=64)
    ap.add_argument("--share-prefix", action="store_true")
    ap.add_argument("--metrics-window", type=float, default=10.0)
    ap.add_argument("--devices", type=int, default=None,
                    help="force this process's host-platform device count "
                         "(the launcher normally sets XLA_FLAGS instead)")
    ap.add_argument("--distributed", action="store_true",
                    help="call jax.distributed.initialize (real multi-host "
                         "meshes only; single-machine clusters never need "
                         "collectives)")
    ap.add_argument("--coordinator", default="127.0.0.1:12345")
    ap.add_argument("--num-processes", type=int, default=1)
    ap.add_argument("--process-id", type=int, default=0)
    args = ap.parse_args()

    if args.devices is not None:
        _apply_device_flags(args.devices)

    import jax                                       # after XLA_FLAGS

    engine = build_engine(args)
    host, port = args.connect.rsplit(":", 1)
    sock = socket.create_connection((host, int(port)))
    sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    stream = MessageStream(sock)
    stream.send({"type": "ready", "replica": args.replica_id,
                 "pid": os.getpid(), "devices": jax.device_count(),
                 "max_len": args.max_len})
    worker = EngineWorker(engine, stream, args.replica_id)
    try:
        worker.serve_forever()
    finally:
        stream.close()


if __name__ == "__main__":
    main()
