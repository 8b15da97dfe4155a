"""Host-0 broadcast dispatch for multi-controller SPMD serving.

In a multi-host slice every controller must enter the SAME compiled
computation in the same order, or the collectives deadlock. Requests only
arrive at host 0 (the router targets its gRPC port alone), so host 0
**broadcasts each step** — which model to run and the batch bytes — to the
secondary controllers, which replay it against their own copy of the model
repo (synced from the same control plane). This replaces the reference
topology's single tritonserver process with one engine process per host
(SURVEY.md §7 hard part 6).

Transport: ``jax.experimental.multihost_utils.broadcast_one_to_all`` — itself
one compiled psum over the global device set, so the control channel rides
the same ICI/DCN fabric as the data. Two rounds per step: a fixed-shape
header [op, nbytes], then the payload padded to the broadcast length every
host now knows.

No NCCL/MPI analog is hand-written; inside the jitted model executable XLA
inserts all collectives from shardings, and this module only sequences WHICH
executable runs.
"""

from __future__ import annotations

import os
import pickle
from typing import Any, Callable, Optional, Tuple

import numpy as np

OP_NOOP = 0
OP_RUN = 1
OP_STOP = 2

# op-code closed world: the declared registry of every step op a follower
# can replay. recv() validates against it, so an op this module cannot name
# (version skew between host 0 and a follower, or header corruption) raises
# UnknownBroadcastOp instead of silently desyncing the follower loop — a
# follower that skips a step host 0 executed deadlocks the slice on the
# next cross-host collective with no diagnostic.
_OP_NAMES = {0: "noop", 1: "run", 2: "stop"}


class UnknownBroadcastOp(RuntimeError):
    """Host 0 broadcast an op code outside the declared closed world."""


def _check_op(op: int) -> int:
    if op not in _OP_NAMES:
        raise UnknownBroadcastOp(
            "broadcast op {} is not in the declared op registry {} — "
            "host 0 and this follower disagree on the step protocol "
            "(version skew?); refusing to guess (a silently skipped step "
            "deadlocks the slice on the next collective)".format(
                op, _OP_NAMES
            )
        )
    return op


class BroadcastChannel:
    """Host-0 -> all-hosts step channel over the global device set."""

    def __init__(self):
        import threading

        import jax

        self._is_source = jax.process_index() == 0
        self.process_count = jax.process_count()
        # host-0 sends come from batcher worker threads AND the reconcile
        # loop; interleaved broadcasts would corrupt the header/payload
        # pairing, so sends serialize
        self._send_lock = threading.Lock()

    @staticmethod
    def _bucket(nbytes: int) -> int:
        """Pad payload broadcasts to power-of-two sizes: broadcast_one_to_all
        jit-compiles per shape, so raw pickle lengths would compile a fresh
        collective for nearly every request; bucketing bounds the cache to
        ~log2(max_payload) executables."""
        size = 64
        while size < nbytes:
            size *= 2
        return size

    def send(self, op: int, payload: bytes = b"") -> None:
        """Host 0 only. Secondary hosts MUST be in recv() concurrently."""
        from jax.experimental import multihost_utils

        with self._send_lock:
            header = np.asarray([op, len(payload)], np.int64)
            multihost_utils.broadcast_one_to_all(header, is_source=self._is_source)
            if payload:
                bucket = self._bucket(len(payload))
                buf = np.zeros(bucket, np.uint8)
                buf[: len(payload)] = np.frombuffer(payload, np.uint8)
                multihost_utils.broadcast_one_to_all(buf, is_source=self._is_source)

    def recv(self) -> Tuple[int, bytes]:
        """Secondary hosts: blocks until host 0 sends the next step."""
        from jax.experimental import multihost_utils

        header = multihost_utils.broadcast_one_to_all(
            np.zeros(2, np.int64), is_source=self._is_source
        )
        # broadcast_one_to_all returns a fully-replicated global value —
        # every host holds the identical header/payload, so the host reads
        # below are multihost-safe by construction
        op, nbytes = int(header[0]), int(header[1])  # tpuserve: ignore[TPU803] header is replicated (broadcast result)
        op = _check_op(op)
        payload = b""
        if nbytes:
            buf = multihost_utils.broadcast_one_to_all(
                np.zeros(self._bucket(nbytes), np.uint8), is_source=self._is_source
            )
            payload = np.asarray(buf, np.uint8)[:nbytes].tobytes()  # tpuserve: ignore[TPU803] buf is replicated (broadcast result)
        return op, payload


class HostZeroDispatcher:
    """Wraps host-0's per-request execution so every step is mirrored to the
    followers BEFORE the local dispatch enters the executable."""

    def __init__(self, channel: Optional[BroadcastChannel] = None):
        import threading

        self.channel = channel or BroadcastChannel()
        self._multi = self.channel.process_count > 1
        # broadcast order MUST equal local execution order: followers replay
        # in broadcast order, and two executables entered in different orders
        # on different hosts deadlock the slice if they contain cross-host
        # collectives — so send+dispatch are one critical section
        self._order_lock = threading.Lock()

    def run(self, key: str, fn: Callable, inputs) -> Any:
        """Broadcast (key, inputs) then execute fn(inputs) locally, atomically
        with respect to other dispatches."""
        if not self._multi:
            return fn(inputs)
        with self._order_lock:
            self.channel.send(OP_RUN, pickle.dumps((key, inputs)))
            return fn(inputs)

    def noop(self) -> None:
        """Heartbeat broadcast, ordered with respect to run()/stop().

        run() releases the channel's send lock before entering the executable
        (still inside _order_lock), so a raw ``channel.send(OP_NOOP)`` from
        another thread could slot its psum between a RUN broadcast and the
        executable's own collectives — host 0 and the followers would then
        enqueue device work in different orders and deadlock the slice.
        """
        if self._multi:
            with self._order_lock:
                self.channel.send(OP_NOOP)

    def stop(self) -> None:
        if self._multi:
            # under the order lock: a queued dispatch must not broadcast
            # AFTER followers exit, or its collective hangs host 0 forever
            with self._order_lock:
                self.channel.send(OP_STOP)


def follower_loop(
    resolve: Callable[[str], Optional[Callable]],
    channel: Optional[BroadcastChannel] = None,
    on_error: Optional[Callable[[str, BaseException], None]] = None,
) -> None:
    """Secondary-controller main loop: replay host-0's steps until OP_STOP.

    ``resolve(key)`` returns the callable for a broadcast step (e.g. the
    repo model's run_batch) or None if this host could not materialize the
    model even after a re-sync. None is a FATAL desync: host 0 is already
    entering the executable, and if it contains cross-host collectives a
    silently-skipping follower hangs the whole slice with no diagnostic.
    We fail loudly instead — raise, crash this controller, and let the
    supervisor restart it into a fresh sync (same crash-and-restart policy
    as HBM OOM; the hang becomes a visible, attributable failure).
    """
    chan = channel or BroadcastChannel()
    while True:
        op, payload = chan.recv()  # raises UnknownBroadcastOp on skew
        if op == OP_STOP:
            return
        if op == OP_NOOP:
            continue
        key, inputs = pickle.loads(payload)
        fn = resolve(key)
        if fn is None:
            raise RuntimeError(
                "follower desync: host 0 dispatched model {!r} but this host "
                "cannot resolve it after re-sync; refusing to silently skip a "
                "broadcast step (slice would deadlock on any cross-host "
                "collective). Restart this controller to re-join.".format(key)
            )
        try:
            fn(inputs)
        except BaseException as ex:  # a follower must never desync the loop
            if on_error is not None:
                on_error(key, ex)


def configure_process_devices(devices: Optional[dict]) -> None:
    """Apply a worker spec's device block before the first jax device use.

    Process-backend replicas (serving/process_replica.py,
    docs/replication.md) run one engine per OS process, each owning its own
    device mesh. On a real slice that partitioning comes from the platform
    (each controller process sees its local chips); on CPU hosts it has to
    be conjured — ``cpu_devices`` forces ``jax_num_cpu_devices`` so a worker
    gets the same N-device mesh the in-process test fixtures configure.

    Must run before anything touches ``jax.devices()``: the XLA CPU client
    is created once per process and never re-reads the flag. Call it first
    thing in the worker main, before the engine module is imported.
    """
    block = devices or {}
    n = int(block.get("cpu_devices") or 0)
    if n > 0:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        import jax

        jax.config.update("jax_num_cpu_devices", n)
