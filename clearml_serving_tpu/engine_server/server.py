"""JAX engine server: gRPC tensor-infer service over the model repo.

Replaces the tritonserver C++ process in the reference topology (SURVEY.md
§2.9 row 1): the router's ``jax_grpc`` client engine sends named typed tensors;
this process owns the TPU devices, runs bucket-compiled XLA executables behind
per-model dynamic batchers, polls the control plane for model changes (hot
swap), and exports Prometheus metrics (request/batch counters + per-chip HBM
gauges) on a sidecar port — the same scrape surface tritonserver exposes
on :8002.

Run: ``python -m clearml_serving_tpu.engine_server.server`` with
``TPUSERVE_SERVICE_ID`` (and optionally ``TPUSERVE_ENGINE_PORT``,
``TPUSERVE_ENGINE_METRICS_PORT``, ``TPUSERVE_POLL_FREQ``).
"""

from __future__ import annotations

import asyncio
import os
import time
from typing import Optional

import grpc
import numpy as np

from . import protocol
from .repo import EngineModelRepo


class _EngineHandler(grpc.GenericRpcHandler):
    """Generic byte-level handler — no protoc codegen (protocol.py docs)."""

    def __init__(self, servicer: "EngineServer"):
        self._servicer = servicer

    def service(self, handler_call_details):
        method = handler_call_details.method
        if method == protocol.INFER_METHOD:
            return grpc.unary_unary_rpc_method_handler(
                self._servicer.infer,
                request_deserializer=None,
                response_serializer=None,
            )
        if method == protocol.STATUS_METHOD:
            return grpc.unary_unary_rpc_method_handler(
                self._servicer.status,
                request_deserializer=None,
                response_serializer=None,
            )
        return None


class EngineMetrics:
    """Per-model gRPC-path observability: latency + queue-delay histograms and
    outcome-labelled request counters (the Triton server exports the
    equivalent nv_inference_{request_duration,queue_duration,count} series —
    triton_helper.py relays them; gauges alone lose rate()/quantile query
    power)."""

    def __init__(self, registry=None):
        from prometheus_client import REGISTRY, Counter, Histogram

        registry = registry if registry is not None else REGISTRY
        self.latency = Histogram(
            "engine_infer_latency_seconds",
            "end-to-end gRPC infer latency",
            ["model"],
            buckets=(0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0),
            registry=registry,
        )
        self.queue_delay = Histogram(
            "engine_queue_delay_seconds",
            "dynamic-batcher queue wait (enqueue to batch start)",
            ["model"],
            buckets=(0.0005, 0.001, 0.002, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25),
            registry=registry,
        )
        self.requests = Counter(
            "engine_infer_requests_total",
            "infer RPCs by outcome",
            ["model", "outcome"],
            registry=registry,
        )
        # padding efficiency of the bucket-padding path: real request rows
        # vs rows added purely to reach the compiled bucket shape. A high
        # padded/real ratio means the bucket set or dynamic-batching knobs
        # are mis-tuned for the traffic (rate() these two against each other)
        self.batch_rows = Counter(
            "engine_batch_rows_total",
            "rows entering executed batches, by kind (real request rows vs "
            "bucket-padding waste)",
            ["model", "kind"],
            registry=registry,
        )

    def wire_batcher(self, name: str, batcher) -> None:
        if batcher.on_queue_delay is None:
            observe = self.queue_delay.labels(model=name).observe
            batcher.on_queue_delay = observe
        if batcher.on_padding is None:
            real_c = self.batch_rows.labels(model=name, kind="real")
            pad_c = self.batch_rows.labels(model=name, kind="padded")

            def on_padding(real_rows: int, padded_rows: int) -> None:
                if real_rows:
                    real_c.inc(real_rows)
                if padded_rows:
                    pad_c.inc(padded_rows)

            batcher.on_padding = on_padding


class EngineServer:
    def __init__(self, repo: EngineModelRepo, metrics: Optional[EngineMetrics] = None):
        self.repo = repo
        self.metrics = metrics

    def _count(self, model_name: str, outcome: str) -> None:
        if self.metrics is not None:
            self.metrics.requests.labels(model=model_name, outcome=outcome).inc()

    async def infer(self, request_bytes: bytes, context) -> bytes:
        tic = time.monotonic()
        try:
            request = protocol.decode_infer_request(request_bytes)
        except Exception as ex:
            self._count("_undecodable", "bad_request")
            await context.abort(
                grpc.StatusCode.INVALID_ARGUMENT, "bad request encoding: {}".format(ex)
            )
        model_name = request["model"]
        model = self.repo.get(model_name, request.get("version") or None)
        if model is None:
            self._count(model_name, "not_found")
            await context.abort(
                grpc.StatusCode.NOT_FOUND,
                "model {!r} version {!r} not loaded (have: {})".format(
                    model_name, request.get("version"), sorted(self.repo.list_models())
                ),
            )
        # metric label = the repo's canonical key, not the client-supplied
        # name: a model reachable under several names (with/without version
        # suffix) must not split or mis-attribute its series
        label = model.key
        if self.metrics is not None:
            self.metrics.wire_batcher(label, model.batcher)
        inputs_by_name = request["inputs"]
        # order inputs per the endpoint spec; single-input models accept any name
        if model.input_names:
            try:
                ordered = [inputs_by_name[name] for name in model.input_names]
            except KeyError as ex:
                self._count(label, "bad_request")
                await context.abort(
                    grpc.StatusCode.INVALID_ARGUMENT,
                    "missing input {} (expected {})".format(ex, model.input_names),
                )
        else:
            ordered = list(inputs_by_name.values())
        try:
            outputs = await model.batcher.infer(ordered)
        except Exception as ex:
            self._count(label, "error")
            await context.abort(
                grpc.StatusCode.INTERNAL, "inference failed: {}".format(ex)
            )
        names = model.output_names
        named = {
            (names[i] if i < len(names) else "output_{}".format(i)): np.asarray(out)
            for i, out in enumerate(outputs)
        }
        self._count(label, "ok")
        if self.metrics is not None:
            self.metrics.latency.labels(model=label).observe(
                time.monotonic() - tic
            )
        return protocol.encode_infer_response(named)

    async def status(self, request_bytes: bytes, context) -> bytes:
        import jax

        return protocol.encode_obj(
            {
                "models": self.repo.list_models(),
                "devices": [str(d) for d in jax.devices()],
                "time": time.time(),
            }
        )


def make_server(
    repo: EngineModelRepo, port: int = 0, metrics: Optional[EngineMetrics] = None
) -> "tuple[grpc.aio.Server, int]":
    server = grpc.aio.server(
        options=[
            ("grpc.max_receive_message_length", 256 * 1024 * 1024),
            ("grpc.max_send_message_length", 256 * 1024 * 1024),
        ]
    )
    server.add_generic_rpc_handlers((_EngineHandler(EngineServer(repo, metrics)),))
    bound_port = server.add_insecure_port("[::]:{}".format(port))
    return server, bound_port


async def serve(service_id: Optional[str] = None) -> None:
    from prometheus_client import Counter, Gauge, start_http_server

    from ..serving.model_request_processor import ModelRequestProcessor
    from ..statistics.metrics import StatisticsController
    from ..utils.tpu import device_memory_stats

    from ..serving.main import maybe_start_profiler

    maybe_start_profiler()
    import jax

    dispatcher = None
    if jax.process_count() > 1:
        from ..parallel.multihost import HostZeroDispatcher

        dispatcher = HostZeroDispatcher()
    processor = ModelRequestProcessor(service_id=service_id)
    repo = EngineModelRepo(processor, dispatcher=dispatcher)
    repo.sync()

    port = int(os.environ.get("TPUSERVE_ENGINE_PORT", 8001))
    metrics_port = int(os.environ.get("TPUSERVE_ENGINE_METRICS_PORT", 8002))
    poll_freq_sec = float(os.environ.get("TPUSERVE_POLL_FREQ", 1.0)) * 60.0

    try:
        start_http_server(metrics_port)
        requests_g = Gauge("engine_requests_served", "requests served", ["model"])
        batches_g = Gauge("engine_batches_executed", "batches executed", ["model"])
        metrics = EngineMetrics()
        hbm = StatisticsController("", processor=None)
    except OSError:
        requests_g = batches_g = hbm = metrics = None

    server, bound = make_server(repo, port, metrics)
    await server.start()
    print("engine server: gRPC on :{} ({} models)".format(bound, len(repo.list_models())))

    async def reconcile_loop():
        while True:
            await asyncio.sleep(poll_freq_sec)
            try:
                try:
                    await asyncio.to_thread(repo.sync)
                finally:
                    if dispatcher is not None:
                        # heartbeat: lets followers leave recv() and re-sync.
                        # Sent even when this host's sync flaked — follower
                        # liveness must not depend on host-0 sync success.
                        # Via the dispatcher so it serializes with in-flight
                        # RUN broadcasts (ordering contract in multihost.py)
                        await asyncio.to_thread(dispatcher.noop)
                if requests_g is not None:
                    for name, info in repo.list_models().items():
                        requests_g.labels(model=name).set(info["requests_served"])
                        batches_g.labels(model=name).set(info["batches_executed"])
                    hbm.update_device_gauges(device_memory_stats())
            except Exception as ex:
                print("engine server reconcile error: {}".format(ex))

    asyncio.get_running_loop().create_task(reconcile_loop())
    try:
        await server.wait_for_termination()
    finally:
        if dispatcher is not None:
            dispatcher.stop()


def serve_follower(service_id: Optional[str] = None) -> None:
    """Secondary-controller main: replay host-0's dispatch steps.

    Binds NO service ports. The follower syncs the same model repo from the
    control plane, then enters the broadcast loop; a NOOP heartbeat from
    host 0's reconcile loop gives it windows to re-sync (hot swaps land on
    all hosts within one poll period)."""
    import jax

    from ..parallel.multihost import follower_loop

    from ..serving.model_request_processor import ModelRequestProcessor

    processor = ModelRequestProcessor(service_id=service_id)
    repo = EngineModelRepo(processor)
    repo.sync()
    print(
        "engine server follower: process {} of {} ({} models)".format(
            jax.process_index(), jax.process_count(), len(repo.list_models())
        )
    )

    def resolve(key: str):
        model = repo.get_by_key(key)
        if model is None:
            # host 0 may have loaded it after our last sync. Retry the sync
            # a few times so one dropped control-plane packet isn't
            # slice-fatal; only after retries is this a real desync, and
            # follower_loop then fails LOUDLY (crash + supervisor restart)
            # rather than silently skipping a broadcast step the rest of
            # the slice is already inside (silent skip = undiagnosable
            # collective deadlock).
            for attempt in range(3):
                try:
                    repo.sync()
                except Exception as ex:
                    print("follower sync error (try {}): {}".format(attempt + 1, ex))
                    time.sleep(0.5 * (attempt + 1))
                    continue
                model = repo.get_by_key(key)
                if model is not None:
                    break
        return model.run_batch if model is not None else None

    from ..parallel import multihost

    class _SyncingChannel(multihost.BroadcastChannel):
        def recv(self):
            op, payload = super().recv()
            if op == multihost.OP_NOOP:
                try:
                    repo.sync()
                except Exception as ex:
                    print("follower sync error: {}".format(ex))
            return op, payload

    follower_loop(
        resolve,
        channel=_SyncingChannel(),
        on_error=lambda key, ex: print(
            "follower: replay of {!r} failed: {}".format(key, ex)
        ),
    )


def main() -> None:
    from ..parallel.distributed import initialize_distributed, is_primary_host

    initialize_distributed()  # no-op single-host; TPUSERVE_COORDINATOR multi-host
    service_id = os.environ.get("TPUSERVE_SERVICE_ID") or None
    if not is_primary_host():
        # Secondary hosts bind NO service ports: they replay host-0's
        # broadcast dispatch steps so every controller of the slice enters
        # the same executables in the same order (multi-controller SPMD,
        # SURVEY.md §7 hard part 6).
        serve_follower(service_id)
        return
    asyncio.run(serve(service_id))


if __name__ == "__main__":
    main()
