import pytest
from prometheus_client import CollectorRegistry

from clearml_serving_tpu.serving.endpoints import EndpointMetricLogging, MetricType
from clearml_serving_tpu.serving.model_request_processor import ModelRequestProcessor
from clearml_serving_tpu.statistics.broker import (
    FileBrokerConsumer,
    FileBrokerProducer,
    make_consumer,
    make_producer,
)
from clearml_serving_tpu.statistics.metrics import StatisticsController


def test_file_broker_roundtrip(tmp_path):
    producer = FileBrokerProducer(str(tmp_path / "b"))
    consumer = FileBrokerConsumer(str(tmp_path / "b"))
    producer.send_batch([{"_url": "e", "_latency": 0.1}, {"_url": "e", "_count": 2}])
    out = consumer.poll()
    assert len(out) == 2
    # offsets: re-poll returns nothing new
    assert consumer.poll() == []
    producer.send_batch([{"_url": "e2"}])
    assert len(consumer.poll()) == 1


def test_broker_url_scheme(tmp_path):
    assert make_producer("") is None
    assert make_consumer("") is None
    p = make_producer("file://{}".format(tmp_path / "x"))
    c = make_consumer("file://{}".format(tmp_path / "x"))
    p.send_batch([{"_url": "a"}])
    assert c.poll() == [{"_url": "a"}]


def _get_sample(registry, name, suffix="", labels=None):
    value = registry.get_sample_value(name + suffix, labels or {})
    return value


def test_statistics_controller(tmp_path, state_root):
    mrp = ModelRequestProcessor(state_root=str(state_root), force_create=True, name="s")
    mrp.add_metric_logging(
        EndpointMetricLogging(
            endpoint="ep1",
            metrics={
                "x0": MetricType(type="scalar", buckets=[0, 1, 2, 3]),
                "label": MetricType(type="enum", buckets=["cat", "dog"]),
                "conf": MetricType(type="value"),
                "hits": MetricType(type="counter"),
            },
        )
    )
    mrp.serialize()

    registry = CollectorRegistry()
    ctl = StatisticsController("file://{}".format(tmp_path / "b"), processor=mrp, registry=registry)
    ctl.sync_specs()
    n = ctl.process_batch(
        [
            {"_url": "ep1", "_latency": 0.05, "_count": 10, "x0": 1.5,
             "label": "cat", "conf": 0.9, "hits": 3},
            {"_url": "ep1", "_latency": 0.2, "_count": 10, "x0": [0.5, 2.5],
             "label": "dog", "conf": 0.4, "hits": 2},
        ]
    )
    assert n == 2
    assert _get_sample(registry, "ep1__latency", "_count") == 2.0
    assert _get_sample(registry, "ep1__count", "_total") == 20.0
    assert _get_sample(registry, "ep1_x0", "_count") == 3.0  # list observed per-value
    # declared-bucket enum -> reference-parity EnumHistogram export shape
    assert _get_sample(registry, "ep1_label", "_bucket", {"enum": "cat"}) == 1.0
    assert _get_sample(registry, "ep1_label", "_bucket", {"enum": "dog"}) == 1.0
    assert _get_sample(registry, "ep1_label", "_sum") == 2.0
    assert _get_sample(registry, "ep1_conf") == 0.4  # gauge keeps last
    assert _get_sample(registry, "ep1_hits", "_total") == 5.0


def test_enum_histogram_semantics(tmp_path, state_root):
    """Declared buckets fix the exported set and ordering (reference
    EnumHistogram); undeclared values are dropped; spec-less enums fall
    back to the labeled Counter."""
    mrp = ModelRequestProcessor(state_root=str(state_root), force_create=True, name="se")
    mrp.add_metric_logging(
        EndpointMetricLogging(
            endpoint="ep2",
            metrics={
                "cls": MetricType(type="enum", buckets=["a", "b", "c"]),
                # single declared bucket: below EnumHistogram's 2-bucket
                # minimum (matches reference), falls back to labeled Counter
                "free": MetricType(type="enum", buckets=["only"]),
            },
        )
    )
    mrp.serialize()
    registry = CollectorRegistry()
    ctl = StatisticsController(
        "file://{}".format(tmp_path / "b"), processor=mrp, registry=registry
    )
    ctl.sync_specs()
    ctl.process_batch(
        [
            {"_url": "ep2", "cls": "b", "free": "anything"},
            {"_url": "ep2", "cls": ["b", "zzz"], "free": "other"},
        ]
    )
    assert _get_sample(registry, "ep2_cls", "_bucket", {"enum": "a"}) == 0.0
    assert _get_sample(registry, "ep2_cls", "_bucket", {"enum": "b"}) == 2.0
    assert _get_sample(registry, "ep2_cls", "_sum") == 2.0  # "zzz" dropped
    # undeclared value has no series at all (fixed bucket set)
    assert _get_sample(registry, "ep2_cls", "_bucket", {"enum": "zzz"}) is None
    # sub-minimum bucket list keeps the dynamic labeled-Counter shape
    assert _get_sample(registry, "ep2_free", "_total", {"value": "anything"}) == 1.0
    assert _get_sample(registry, "ep2_free", "_total", {"value": "other"}) == 1.0


def test_unknown_endpoint_reserved_only(tmp_path, state_root):
    mrp = ModelRequestProcessor(state_root=str(state_root), force_create=True, name="s2")
    mrp.serialize()
    registry = CollectorRegistry()
    ctl = StatisticsController("file://{}".format(tmp_path / "b"), processor=mrp, registry=registry)
    ctl.sync_specs()
    ctl.process_batch([{"_url": "mystery", "_latency": 0.1, "_count": 1, "custom": 5}])
    assert _get_sample(registry, "mystery__latency", "_count") == 1.0
    # unknown variable without a spec is dropped
    assert _get_sample(registry, "mystery_custom") is None


def test_device_gauges_take_rows_from_the_chip_owner(tmp_path):
    """The controller exports what the chip-owning process hands it
    (utils.tpu.device_memory_stats rows); a CPU row carries only its id."""
    registry = CollectorRegistry()
    ctl = StatisticsController("", registry=registry)
    ctl.update_device_gauges(
        [{"id": 0, "bytes_in_use": 5, "bytes_limit": 9}, {"id": 1}]
    )
    assert registry.get_sample_value("tpu_hbm_bytes_in_use", {"device": "0"}) == 5
    assert registry.get_sample_value("tpu_hbm_bytes_limit", {"device": "0"}) == 9
    assert registry.get_sample_value("tpu_hbm_bytes_in_use", {"device": "1"}) is None


def test_statistics_package_never_imports_jax():
    """A chip belongs to one process: the statistics service runs beside
    the process that owns it, so nothing in this package may import jax —
    not at module level and not lazily."""
    import pathlib
    import re

    import clearml_serving_tpu.statistics as pkg

    pattern = re.compile(r"^\s*(import|from)\s+jax\b", re.M)
    for path in pathlib.Path(pkg.__file__).parent.glob("*.py"):
        assert not pattern.search(path.read_text()), path


def test_prefix_cache_collector_exports_live_counters():
    """The radix prefix cache's hit/miss/eviction counters and the page
    pool's sharing/CoW gauges are scraped live (no push path needed)."""
    import numpy as np

    from clearml_serving_tpu.llm.kv_cache import PagePool
    from clearml_serving_tpu.llm.prefix_cache import RadixPrefixCache
    from clearml_serving_tpu.statistics.metrics import register_prefix_cache

    pool = PagePool(num_pages=16, page_size=2, max_slots=2)
    cache = RadixPrefixCache(block=4, pool=pool, page_bytes=32)
    registry = CollectorRegistry()
    register_prefix_cache(cache, pool, registry=registry, key="m1")

    ids = [1, 2, 3, 4, 5, 6]
    assert cache.lookup_pages(ids, 0) is None          # miss
    pool.allocate(0, 6)
    cache.store_pages(ids, 0, pool.slot_pages(0))
    hit = cache.lookup_pages(ids, 0)                   # hit (4 tokens)
    cache.release(hit)

    def val(name, key="m1"):
        return registry.get_sample_value(name, {"model": key})

    def hits_val(key="m1", tier="hbm"):
        # the hit counter carries a serving-tier label (docs/kv_tiering.md)
        return registry.get_sample_value(
            "llm_prefix_cache_hits_total", {"model": key, "tier": tier}
        )

    assert hits_val() == 1
    assert val("llm_prefix_cache_misses_total") == 1
    assert val("llm_prefix_cache_hit_tokens_total") == 4
    assert val("llm_prefix_cache_nodes") == 1
    assert val("llm_prefix_cache_pages") == 2
    assert val("llm_prefix_cache_bytes") == 64
    assert val("kv_pool_shared_pages") == 2            # slot + cache refs
    assert val("kv_pool_cow_events_total") == 0
    assert val("kv_pool_free_pages") == pool.free_pages

    # dense-backend registration (no pool) lands on the SAME collector
    # under its own model label; re-registering a key REPLACES the entry
    # (engine hot-reload must not leak the old cache or split series)
    dense = RadixPrefixCache(block=2)
    c2 = register_prefix_cache(dense, registry=registry, key="m2")
    k = np.zeros((1, 1, 4, 1, 2), np.float32)
    dense.store([1, 2, 3], 0, {"k": k, "v": k})
    assert dense.lookup([1, 2, 9], 0) is not None
    assert hits_val("m2") == 1
    assert val("kv_pool_shared_pages", "m2") is None
    assert hits_val("m1") == 1  # m1 intact

    fresh = RadixPrefixCache(block=2)
    c3 = register_prefix_cache(fresh, registry=registry, key="m2")
    assert c3 is c2  # same collector, entry swapped
    assert hits_val("m2") == 0


def test_prefix_cache_collector_skips_stats_less_probes():
    """The process backend's routing-only prefix probe has no ``stats``
    surface (the real cache lives in the worker; its stats come back over
    the health RPC). A registered stats-less entry must not poison the
    whole registry scrape — and real entries keep exporting."""
    from prometheus_client import generate_latest

    from clearml_serving_tpu.llm.prefix_cache import RadixPrefixCache
    from clearml_serving_tpu.serving.process_replica import _PrefixProbe
    from clearml_serving_tpu.statistics.metrics import register_prefix_cache

    probe = _PrefixProbe(object(), block=16)
    assert not hasattr(probe, "stats")  # the premise this test pins

    registry = CollectorRegistry()
    cache = RadixPrefixCache(block=2)
    register_prefix_cache(cache, registry=registry, key="real")
    register_prefix_cache(probe, registry=registry, key="worker@r0",
                          model="worker", replica="r0")

    blob = generate_latest(registry).decode()  # must not raise
    assert 'model="real"' in blob
    assert "worker@r0" not in blob


def test_engine_lifecycle_collector_exports_counters_and_gauges():
    """Shed/deadline/watchdog counters and the queue-depth / active-slot
    gauges scrape live from a provider callable (the engine's
    lifecycle_stats, or the gRPC client's retry stats)."""
    from clearml_serving_tpu.statistics.metrics import register_engine_lifecycle

    stats = {
        "queue_depth": 3,
        "active_slots": 2,
        "ready": 1,
        "sheds": {"queue": 4, "pool": 1},
        "deadlines": {"queue": 2, "ttft": 1, "total": 5},
        "watchdog_trips": 1,
        "step_failures": 2,
    }
    registry = CollectorRegistry()
    collector = register_engine_lifecycle(
        lambda: stats, registry=registry, key="m1"
    )

    def val(name, **labels):
        return registry.get_sample_value(name, {"model": "m1", **labels})

    # plain queue_depth ints land under class="all" (legacy providers);
    # per-class series come from a queue_depths dict (see the SLO test)
    assert val("engine_queue_depth", **{"class": "all"}) == 3
    assert val("engine_active_slots") == 2
    assert val("engine_ready") == 1
    assert val("engine_sheds_total", reason="queue", **{"class": "all"}) == 4
    assert val("engine_sheds_total", reason="pool", **{"class": "all"}) == 1
    assert val("engine_deadline_hits_total", stage="ttft") == 1
    assert val("engine_watchdog_trips_total") == 1
    assert val("engine_step_failures_total") == 2

    # gauges move on the next scrape (read live, not pushed)
    stats["queue_depth"] = 7
    assert val("engine_queue_depth", **{"class": "all"}) == 7

    # the gRPC client's retry stats ride the same collector
    from clearml_serving_tpu.engines.grpc_client import grpc_lifecycle_stats

    c2 = register_engine_lifecycle(
        grpc_lifecycle_stats, registry=registry, key="grpc"
    )
    assert c2 is collector
    assert registry.get_sample_value(
        "grpc_client_upstream_total", {"model": "grpc", "kind": "retries"}
    ) is not None

    # re-registering a key replaces the provider (hot-reload semantics)
    register_engine_lifecycle(
        lambda: {"queue_depth": 0, "active_slots": 0}, registry=registry,
        key="m1",
    )
    assert val("engine_queue_depth", **{"class": "all"}) == 0


def test_engine_pipeline_metrics_exported():
    """Pipelined-decode observability (docs/pipelined_decode.md): the
    lifecycle collector exports the in-flight gauge, the configured depth,
    and the dispatch/retire stage histograms from the provider's
    ``pipeline`` block — cumulative Prometheus buckets built from the
    engine's fixed-bucket snapshots."""
    from clearml_serving_tpu.statistics.metrics import register_engine_lifecycle

    snap = {
        "buckets": [1.0, 2.5, 5.0],
        "counts": [2, 1, 0, 3],  # last bucket = +Inf overflow
        "sum_ms": 40.0,
        "count": 6,
    }
    stats = {
        "queue_depth": 0,
        "active_slots": 1,
        "ready": 1,
        "pipeline": {
            "depth": 2,
            "inflight": 1,
            "dispatch_ms": snap,
            "retire_ms": {"buckets": [1.0], "counts": [5, 0],
                          "sum_ms": 2.5, "count": 5},
        },
    }
    registry = CollectorRegistry()
    register_engine_lifecycle(lambda: stats, registry=registry, key="m1")

    def val(name, **labels):
        return registry.get_sample_value(name, {"model": "m1", **labels})

    assert val("engine_pipeline_inflight") == 1
    assert val("engine_pipeline_depth") == 2
    # cumulative histogram semantics: le buckets accumulate, +Inf = count
    assert val("engine_step_dispatch_ms_bucket", le="1.0") == 2
    assert val("engine_step_dispatch_ms_bucket", le="2.5") == 3
    assert val("engine_step_dispatch_ms_bucket", le="5.0") == 3
    assert val("engine_step_dispatch_ms_bucket", le="+Inf") == 6
    assert val("engine_step_dispatch_ms_sum") == 40.0
    assert val("engine_step_retire_ms_bucket", le="+Inf") == 5
    assert val("engine_step_retire_ms_sum") == 2.5
    # the in-flight gauge reads live on every scrape
    stats["pipeline"]["inflight"] = 0
    assert val("engine_pipeline_inflight") == 0
    # providers without a pipeline block keep the historical families only
    registry2 = CollectorRegistry()
    register_engine_lifecycle(
        lambda: {"queue_depth": 1}, registry=registry2, key="m2"
    )
    assert registry2.get_sample_value(
        "engine_pipeline_inflight", {"model": "m2"}
    ) is None


def _phase_snap(i):
    return {"buckets": [1.0, 10.0], "counts": [i, 1, 2],
            "sum_ms": 10.0 * i + 3.0, "count": i + 3}


@pytest.mark.parametrize("family,block,phase,index", [
    ("engine_step_phase_ms", "phases", p, i) for i, p in enumerate(
        ("admin", "plan", "launch", "wait", "emit", "yield", "cycle"))
] + [
    ("engine_request_phase_ms", "requests", p, i) for i, p in enumerate(
        ("queue_wait", "admit", "prefill", "ttft", "first_launch_wait",
         "prefill_span", "first_emit"))
] + [("engine_request_prefill_launches", "requests", None, 5)] + [
    ("engine_launch_part_ms", "launch_parts", p, i) for i, p in enumerate(
        ("hop_out", "upload", "enqueue", "tail", "hop_back"))
] + [("engine_device_starve_ms", "starve_ms", None, 4)])
def test_engine_phase_families_exported(family, block, phase, index):
    """The cycle clock's phases and a request's way to its first token
    (docs/pipelined_decode.md "Observability"): one histogram family each,
    labelled by phase, built from the snapshots like
    engine_step_dispatch_ms; a fleet row carries its replica label, and a
    provider without the blocks keeps the historical families only."""
    from clearml_serving_tpu.statistics.metrics import register_engine_lifecycle

    steps = ("admin", "plan", "launch", "wait", "emit", "yield")
    reqs = ("queue_wait", "admit", "prefill", "ttft", "first_launch_wait",
            "prefill_span", "first_emit")
    parts = ("hop_out", "upload", "enqueue", "tail", "hop_back")
    stats = {
        "queue_depth": 0,
        "replica": "r1",
        "pipeline": {
            "depth": 2, "inflight": 0,
            "phases": {p + "_ms": _phase_snap(i) for i, p in enumerate(steps)},
            "cycle_ms": _phase_snap(6),
            # the launch timeline (tests/test_launch_timeline.py)
            "launch_parts": {p + "_ms": _phase_snap(i)
                             for i, p in enumerate(parts)},
            "readback_ms": _phase_snap(3),       # no family: a benchmark reads it
            "starve_ms": _phase_snap(4),
        },
        "requests": dict(
            {p + "_ms": _phase_snap(i) for i, p in enumerate(reqs)},
            prefill_launches=_phase_snap(5),
        ),
    }
    registry = CollectorRegistry()
    register_engine_lifecycle(lambda: stats, registry=registry, key="m1")
    labels = {"model": "m1", "replica": "r1"}
    if phase is not None:
        labels["part" if block == "launch_parts" else "phase"] = phase

    def val(suffix, **extra):
        return registry.get_sample_value(family + suffix, {**labels, **extra})

    assert val("_bucket", le="1.0") == index
    assert val("_bucket", le="10.0") == index + 1
    assert val("_bucket", le="+Inf") == val("_count") == index + 3
    assert val("_sum") == 10.0 * index + 3.0
    registry2 = CollectorRegistry()
    register_engine_lifecycle(
        lambda: {"queue_depth": 1, "pipeline": {"depth": 2}},
        registry=registry2, key="m2",
    )
    assert registry2.get_sample_value(
        family + "_count", {k: v for k, v in labels.items() if k != "replica"}
        | {"model": "m2"}
    ) is None


def test_engine_sharding_metrics_exported():
    """Sharding-discipline observability (docs/static_analysis.md TPU8xx):
    the lifecycle collector exports the sentry's audit counter and the two
    violation classes from the provider's ``sharding`` block; providers
    without the block (sentry unarmed) keep the historical families only."""
    from clearml_serving_tpu.statistics.metrics import register_engine_lifecycle

    stats = {
        "queue_depth": 0,
        "sharding": {
            "mode": "audit",
            "strict": False,
            "audits": 12,
            "arrays_checked": 57,
            "implicit_transfers": 1,
            "unplanned_reshards": 0,
            "declared_paths": 25,
            "violations": 0,
        },
    }
    registry = CollectorRegistry()
    register_engine_lifecycle(lambda: stats, registry=registry, key="m1")

    def val(name, **labels):
        return registry.get_sample_value(name, {"model": "m1", **labels})

    assert val("engine_shard_audits_total") == 12
    assert val("engine_shard_violations_total",
               kind="implicit_transfer") == 1
    assert val("engine_shard_violations_total",
               kind="unplanned_reshard") == 0

    registry2 = CollectorRegistry()
    register_engine_lifecycle(
        lambda: {"queue_depth": 1, "sharding": None},
        registry=registry2, key="m2",
    )
    assert registry2.get_sample_value(
        "engine_shard_audits_total", {"model": "m2"}
    ) is None


def test_engine_slo_metrics_exported():
    """SLO-scheduling observability (docs/slo_scheduling.md): per-class
    queue depths, per-(reason, class) sheds, the preemption counter and the
    brownout stage/score gauges — from a synthetic provider AND end to end
    against a real engine's lifecycle_stats()."""
    from clearml_serving_tpu.statistics.metrics import register_engine_lifecycle

    stats = {
        "queue_depth": 5,
        "queue_depths": {"interactive": 3, "batch": 2, "best_effort": 0},
        "sheds": {"queue": 3, "pool": 0},
        "sheds_by_class": {
            "queue": {"best_effort": 2, "batch": 1},
            "brownout": {"best_effort": 4},
        },
        "preemptions": 6,
        "brownout": {"stage": 2, "score": 0.91, "signals": {"queue": 0.91}},
    }
    registry = CollectorRegistry()
    register_engine_lifecycle(lambda: stats, registry=registry, key="m1")

    def val(name, **labels):
        return registry.get_sample_value(name, {"model": "m1", **labels})

    assert val("engine_queue_depth", **{"class": "interactive"}) == 3
    assert val("engine_queue_depth", **{"class": "batch"}) == 2
    assert val("engine_queue_depth", **{"class": "all"}) == 5
    assert val("engine_sheds_total", reason="queue",
               **{"class": "best_effort"}) == 2
    assert val("engine_sheds_total", reason="brownout",
               **{"class": "best_effort"}) == 4
    assert val("engine_preemptions_total") == 6
    assert val("engine_brownout_stage") == 2
    assert val("engine_brownout_score") == 0.91
    # the stage gauge reads live on the next scrape
    stats["brownout"]["stage"] = 0
    assert val("engine_brownout_stage") == 0

    # providers without the SLO block keep the historical families only
    registry2 = CollectorRegistry()
    register_engine_lifecycle(
        lambda: {"queue_depth": 1}, registry=registry2, key="m2"
    )
    assert registry2.get_sample_value(
        "engine_preemptions_total", {"model": "m2"}
    ) is None

    # end to end against a REAL engine with admission control (brownout
    # enabled by default when max_pending is set)
    import asyncio

    import jax

    from clearml_serving_tpu import models
    from clearml_serving_tpu.errors import EngineOverloadedError
    from clearml_serving_tpu.llm.engine import GenRequest, LLMEngineCore

    bundle = models.build_model(
        "llama", {"preset": "llama-tiny", "dtype": "float32"}
    )
    params = bundle.init(jax.random.PRNGKey(0))
    engine = LLMEngineCore(
        bundle, params, max_batch=2, max_seq_len=64,
        prefill_buckets=[16], eos_token_id=None, max_pending=1,
        # brownout OFF: this test exercises the QUEUE-full class shed —
        # with the controller live, a full 1-deep queue scores 1.0 and
        # whether C sheds under reason="queue" or reason="brownout"
        # depends on the controller's 0.1 s refresh throttle (an observed
        # under-load flake); brownout shedding has its own tests
        brownout=False,
    )
    try:
        registry3 = CollectorRegistry()
        register_engine_lifecycle(
            engine.lifecycle_stats, registry=registry3, key="llm"
        )

        async def run():
            a = GenRequest(prompt_ids=[1, 2], max_new_tokens=10_000)
            agen = engine.generate(a)
            await agen.__anext__()  # A holds a slot
            # A2 holds the OTHER slot (max_batch=2): without it, the loop
            # can admit B between the queue-depth check below and C's
            # arrival, and C then queues instead of shedding (observed as
            # a rare under-load flake)
            a2 = GenRequest(prompt_ids=[1, 5], max_new_tokens=10_000)
            agen2 = engine.generate(a2)
            await agen2.__anext__()
            b = GenRequest(
                prompt_ids=[1, 3], max_new_tokens=2, priority="batch"
            )
            b_task = asyncio.create_task(
                engine.generate(b).__anext__()
            )
            while engine._pending.qsize() < 1:
                await asyncio.sleep(0.005)
            # queue at the bound: a best_effort arrival sheds
            c = GenRequest(
                prompt_ids=[1, 4], max_new_tokens=2, priority="best_effort"
            )
            try:
                async for _ in engine.generate(c):
                    pass
            except EngineOverloadedError:
                pass
            b_task.cancel()
            try:
                await b_task
            except (asyncio.CancelledError, Exception):
                pass
            await agen.aclose()
            await agen2.aclose()

        asyncio.run(run())

        def rval(name, **labels):
            return registry3.get_sample_value(name, {"model": "llm", **labels})

        # per-class depths export live (batch request parked or drained by
        # now — the family exists with all three classes)
        for cls in ("interactive", "batch", "best_effort"):
            assert rval("engine_queue_depth", **{"class": cls}) is not None
        assert rval(
            "engine_sheds_total", reason="queue", **{"class": "best_effort"}
        ) == 1
        assert rval("engine_preemptions_total") == 0
        # brownout disabled on this engine (determinism note above): the
        # stage gauge must be absent, not zero — the synthetic provider
        # half of this test covers the live-gauge path
        assert rval("engine_brownout_stage") is None
    finally:
        engine.stop()


def test_engine_kv_pool_metrics_exported():
    """Paged-pool capacity observability (docs/paged_kv_quant.md): the
    lifecycle collector exports engine_kv_pool_bytes{kind=kv|scale} and the
    engine_kv_pool_dtype info gauge from the provider's ``kv_pool`` block —
    the int8 halving must be visible on a dashboard, live against a real
    engine's lifecycle_stats()."""
    from clearml_serving_tpu.statistics.metrics import register_engine_lifecycle

    stats = {
        "queue_depth": 0,
        "kv_pool": {
            "kv": 1024, "scale": 256, "dtype": "int8",
            "num_pages": 8, "page_size": 16,
        },
    }
    registry = CollectorRegistry()
    register_engine_lifecycle(lambda: stats, registry=registry, key="m1")

    def val(name, **labels):
        return registry.get_sample_value(name, {"model": "m1", **labels})

    assert val("engine_kv_pool_bytes", kind="kv") == 1024
    assert val("engine_kv_pool_bytes", kind="scale") == 256
    assert val("engine_kv_pool_dtype", dtype="int8") == 1
    # dense-backend providers (kv_pool None) export no pool families
    registry2 = CollectorRegistry()
    register_engine_lifecycle(
        lambda: {"queue_depth": 1, "kv_pool": None},
        registry=registry2, key="m2",
    )
    assert registry2.get_sample_value(
        "engine_kv_pool_bytes", {"model": "m2", "kind": "kv"}
    ) is None

    # end to end against a REAL int8 paged engine's lifecycle_stats
    import jax

    from clearml_serving_tpu import models
    from clearml_serving_tpu.llm.engine import LLMEngineCore

    bundle = models.build_model(
        "llama", {"preset": "llama-tiny", "dtype": "float32",
                  "kv_quant": "int8"}
    )
    params = bundle.init(jax.random.PRNGKey(0))
    engine = LLMEngineCore(
        bundle, params, max_batch=2, max_seq_len=64,
        prefill_buckets=[16], eos_token_id=None, cache_mode="paged",
    )
    try:
        registry3 = CollectorRegistry()
        register_engine_lifecycle(
            engine.lifecycle_stats, registry=registry3, key="llm"
        )
        expect = engine.paged_cache.pool_bytes()
        assert registry3.get_sample_value(
            "engine_kv_pool_bytes", {"model": "llm", "kind": "kv"}
        ) == expect["kv"]
        assert registry3.get_sample_value(
            "engine_kv_pool_bytes", {"model": "llm", "kind": "scale"}
        ) == expect["scale"]
        assert expect["scale"] > 0
        assert registry3.get_sample_value(
            "engine_kv_pool_dtype", {"model": "llm", "dtype": "int8"}
        ) == 1
    finally:
        engine.stop()


def test_engine_ragged_metrics_exported():
    """Ragged-scheduler observability (docs/ragged_attention.md): the
    step-token-budget utilization histogram, per-phase row counters, the
    live job gauge and the effective-budget gauge — from a synthetic
    provider AND end to end against a real ragged engine."""
    from clearml_serving_tpu.statistics.metrics import register_engine_lifecycle

    stats = {
        "queue_depth": 0,
        "ragged": {
            "step_token_budget": 64,
            "effective_budget": 48,
            "prefill_jobs": 2,
            "steps": 7,
            "budget_utilization": {
                "buckets": [0.1, 0.25, 0.5, 0.75, 0.9, 1.0],
                "counts": [0, 1, 2, 3, 1, 0, 0],
                "sum_ms": 4.25,
                "count": 7,
            },
            "step_rows": {"prefill": 9, "decode": 21, "spec_verify": 4},
            # multi-step / spec-as-row families (ISSUE 13)
            "decode_steps": 4,
            "decode_tokens": 57,
            "tokens_per_launch": {
                "buckets": [1, 2, 4, 8, 16, 32, 64],
                "counts": [1, 1, 3, 2, 0, 0, 0, 0],
                "sum_ms": 57.0,
                "count": 7,
            },
            "spec_acceptance": {
                "buckets": [0.0, 0.2, 0.4, 0.6, 0.8, 1.0],
                "counts": [1, 0, 1, 0, 0, 2, 0],
                "sum_ms": 2.5,
                "count": 4,
            },
        },
    }
    registry = CollectorRegistry()
    register_engine_lifecycle(lambda: stats, registry=registry, key="m1")

    def val(name, **labels):
        return registry.get_sample_value(name, {"model": "m1", **labels})

    assert val("engine_step_rows_total", phase="prefill") == 9
    assert val("engine_step_rows_total", phase="decode") == 21
    assert val("engine_step_rows_total", phase="spec_verify") == 4
    assert val("engine_ragged_prefill_jobs") == 2
    assert val("engine_step_token_budget") == 48
    # histogram: cumulative buckets + count/sum
    assert registry.get_sample_value(
        "engine_step_token_budget_utilization_bucket",
        {"model": "m1", "le": "0.75"},
    ) == 6
    assert registry.get_sample_value(
        "engine_step_token_budget_utilization_count", {"model": "m1"}
    ) == 7
    # decode tokens per launch: the dispatch-bubble amortization headline
    assert registry.get_sample_value(
        "engine_decode_tokens_per_launch_count", {"model": "m1"}
    ) == 7
    assert registry.get_sample_value(
        "engine_decode_tokens_per_launch_sum", {"model": "m1"}
    ) == 57.0
    assert registry.get_sample_value(
        "engine_decode_tokens_per_launch_bucket", {"model": "m1", "le": "4"}
    ) == 5
    # per-launch spec acceptance fraction
    assert registry.get_sample_value(
        "engine_spec_acceptance_rate_count", {"model": "m1"}
    ) == 4
    assert registry.get_sample_value(
        "engine_spec_acceptance_rate_bucket", {"model": "m1", "le": "0.4"}
    ) == 2

    # providers without the block (legacy scheduler) skip the families
    registry2 = CollectorRegistry()
    register_engine_lifecycle(
        lambda: {"queue_depth": 1, "ragged": None}, registry=registry2,
        key="m2",
    )
    assert registry2.get_sample_value(
        "engine_ragged_prefill_jobs", {"model": "m2"}
    ) is None

    # end to end: a real ragged engine's lifecycle_stats() feeds the same
    # families after serving one request
    import asyncio

    import jax

    from clearml_serving_tpu import models
    from clearml_serving_tpu.llm.engine import GenRequest, LLMEngineCore

    bundle = models.build_model(
        "llama", {"preset": "llama-tiny", "dtype": "float32"}
    )
    params = bundle.init(jax.random.PRNGKey(0))
    engine = LLMEngineCore(
        bundle, params, max_batch=2, max_seq_len=64, prefill_buckets=[16],
        eos_token_id=None, scheduler="ragged", step_token_budget=8,
        cache_mode="paged", speculation="ngram", spec_k=2, spec_ngram=2,
    )
    try:
        registry3 = CollectorRegistry()
        register_engine_lifecycle(
            engine.lifecycle_stats, registry=registry3, key="llm"
        )

        async def run():
            # a repetitive prompt so the n-gram proposer drafts: decode
            # rides the mixed launches as spec verify rows
            req = GenRequest(prompt_ids=[1, 2, 3, 1, 2, 3], max_new_tokens=6)
            out = [t async for t in engine.generate(req)]
            await engine.wait_drained()
            return out

        out = asyncio.run(run())
        assert len(out) == 6

        def rval(name, **labels):
            return registry3.get_sample_value(name, {"model": "llm", **labels})

        assert rval("engine_step_rows_total", phase="prefill") >= 1
        assert rval("engine_step_rows_total", phase="spec_verify") >= 1
        assert rval("engine_step_token_budget") == 8
        assert rval("engine_ragged_prefill_jobs") == 0
        assert registry3.get_sample_value(
            "engine_step_token_budget_utilization_count", {"model": "llm"}
        ) >= 1
        assert registry3.get_sample_value(
            "engine_decode_tokens_per_launch_count", {"model": "llm"}
        ) >= 1
        assert registry3.get_sample_value(
            "engine_spec_acceptance_rate_count", {"model": "llm"}
        ) >= 1
    finally:
        engine.stop()


def test_engine_kv_tier_metrics_exported():
    """Host-RAM KV tier observability (docs/kv_tiering.md): the lifecycle
    collector exports engine_kv_tier_pages{tier} / engine_kv_tier_bytes
    {tier} gauges and the engine_kv_demotions_total /
    engine_kv_promotions_total counters from the provider's ``kv_tier``
    block; the prefix-cache hit counter carries the serving tier. Checked
    from a synthetic provider AND end to end against a real tiered engine
    that demoted and promoted a run."""
    from clearml_serving_tpu.statistics.metrics import register_engine_lifecycle

    stats = {
        "queue_depth": 0,
        "kv_tier": {
            "pages": {"hbm": 4, "host": 12},
            "bytes": {"hbm": 1024, "host": 3072},
            "demotions": 9, "promotions": 3,
        },
    }
    registry = CollectorRegistry()
    register_engine_lifecycle(lambda: stats, registry=registry, key="m1")

    def val(name, **labels):
        return registry.get_sample_value(name, {"model": "m1", **labels})

    assert val("engine_kv_tier_pages", tier="hbm") == 4
    assert val("engine_kv_tier_pages", tier="host") == 12
    assert val("engine_kv_tier_bytes", tier="host") == 3072
    assert val("engine_kv_demotions_total") == 9
    assert val("engine_kv_promotions_total") == 3

    # untiered providers (kv_tier None) export no tier families
    registry2 = CollectorRegistry()
    register_engine_lifecycle(
        lambda: {"queue_depth": 1, "kv_tier": None}, registry=registry2,
        key="m2",
    )
    assert registry2.get_sample_value(
        "engine_kv_tier_pages", {"model": "m2", "tier": "hbm"}
    ) is None

    # end to end: a real tiered engine after a demote -> promote cycle
    import asyncio

    import jax

    from clearml_serving_tpu import models
    from clearml_serving_tpu.llm.engine import GenRequest, LLMEngineCore
    from clearml_serving_tpu.statistics.metrics import register_prefix_cache

    bundle = models.build_model(
        "llama", {"preset": "llama-tiny", "dtype": "float32",
                  "kv_quant": "int8"}
    )
    params = bundle.init(jax.random.PRNGKey(0))
    engine = LLMEngineCore(
        bundle, params, max_batch=2, max_seq_len=96,
        prefill_buckets=[16, 64], eos_token_id=None, cache_mode="paged",
        prefix_cache=64, prefix_block=16, prefix_cache_host_pages=16,
    )
    try:
        registry3 = CollectorRegistry()
        register_engine_lifecycle(
            engine.lifecycle_stats, registry=registry3, key="llm"
        )
        register_prefix_cache(
            engine._prefix, engine.paged_cache.pool, registry=registry3,
            key="llm",
        )
        prompt = [(7 * i + 3) % 100 + 1 for i in range(40)]

        async def run():
            req = GenRequest(prompt_ids=list(prompt), max_new_tokens=3)
            out = [t async for t in engine.generate(req)]
            await engine.wait_drained()
            return out

        asyncio.run(run())
        assert engine._prefix.spill(0) == 2
        asyncio.run(run())  # warm revisit: host-tier hit promotes

        def rval(name, **labels):
            return registry3.get_sample_value(name, {"model": "llm", **labels})

        assert rval("engine_kv_tier_pages", tier="hbm") == 2  # promoted back
        assert rval("engine_kv_tier_pages", tier="host") == 0
        assert rval("engine_kv_tier_bytes", tier="hbm") > 0
        assert rval("engine_kv_demotions_total") == 1  # one batched round
        assert rval("engine_kv_promotions_total") == 1
        # the prefix-cache hit counter carries the serving tier
        assert rval("llm_prefix_cache_hits_total", tier="host") == 1
        assert rval("llm_prefix_cache_hits_total", tier="hbm") == 0
    finally:
        engine.stop()


def test_engine_compile_metrics_exported(monkeypatch):
    """Compile-surface observability (docs/static_analysis.md TPU6xx): the
    lifecycle collector exports engine_xla_compiles_total{phase} and the
    engine_xla_compile_ms histogram from the provider's ``compile`` block —
    from a synthetic provider AND end to end against a real engine with the
    compile sentry armed."""
    from clearml_serving_tpu.statistics.metrics import register_engine_lifecycle

    stats = {
        "queue_depth": 0,
        "compile": {
            "strict": False, "fenced": True,
            "warmup": 7, "serve": 2, "violations": 0,
            "compile_ms": {
                "buckets": [10.0, 50.0],
                "counts": [3, 4, 2],
                "sum_ms": 431.0,
            },
        },
    }
    registry = CollectorRegistry()
    register_engine_lifecycle(lambda: stats, registry=registry, key="m1")

    def val(name, **labels):
        return registry.get_sample_value(name, {"model": "m1", **labels})

    assert val("engine_xla_compiles_total", phase="warmup") == 7
    assert val("engine_xla_compiles_total", phase="serve") == 2
    assert registry.get_sample_value(
        "engine_xla_compile_ms_bucket", {"model": "m1", "le": "50.0"}
    ) == 7  # cumulative: 3 + 4
    assert registry.get_sample_value(
        "engine_xla_compile_ms_sum", {"model": "m1"}
    ) == 431.0
    # unarmed providers (compile None) export no compile families
    registry2 = CollectorRegistry()
    register_engine_lifecycle(
        lambda: {"queue_depth": 1, "compile": None},
        registry=registry2, key="m2",
    )
    assert registry2.get_sample_value(
        "engine_xla_compiles_total", {"model": "m2", "phase": "warmup"}
    ) is None

    # end to end against a REAL engine with the sentry armed: the engine's
    # lifecycle_stats carries the live sentry block, and a fresh compile in
    # the process bumps the exported warmup counter
    import jax
    import jax.numpy as jnp

    from clearml_serving_tpu import models
    from clearml_serving_tpu.llm import compile_sentry
    from clearml_serving_tpu.llm.engine import LLMEngineCore

    monkeypatch.setenv("TPUSERVE_COMPILE_SENTRY", "1")
    sentry = compile_sentry.get()
    sentry.reset(strict=False)
    bundle = models.build_model(
        "llama", {"preset": "llama-tiny", "dtype": "float32"}
    )
    params = bundle.init(jax.random.PRNGKey(0))
    engine = LLMEngineCore(
        bundle, params, max_batch=2, max_seq_len=64,
        prefill_buckets=[16], eos_token_id=None,
    )
    try:
        assert engine._compile_sentry is sentry
        registry3 = CollectorRegistry()
        register_engine_lifecycle(
            engine.lifecycle_stats, registry=registry3, key="llm"
        )
        jax.jit(lambda x: x * 17)(jnp.ones((3,)))  # fresh lambda: compiles
        count = registry3.get_sample_value(
            "engine_xla_compiles_total", {"model": "llm", "phase": "warmup"}
        )
        assert count is not None and count >= 1
        assert registry3.get_sample_value(
            "engine_xla_compiles_total", {"model": "llm", "phase": "serve"}
        ) == 0
    finally:
        engine.stop()
        sentry.reset(strict=False)


def test_engine_ledger_metrics_exported(monkeypatch):
    """Ownership-discipline observability (docs/static_analysis.md TPU7xx):
    the lifecycle collector exports engine_ledger_outstanding{resource} and
    engine_ledger_leaks_total from the provider's ``ledger`` block — from a
    synthetic provider AND end to end against a real engine with the
    ownership ledger armed."""
    from clearml_serving_tpu.statistics.metrics import register_engine_lifecycle

    stats = {
        "queue_depth": 0,
        "ledger": {
            "strict": True, "acquires": 40, "releases": 37,
            "leaks": 2, "double_releases": 1, "violations": 3,
            "outstanding": {"pages.slot": 0, "pages.ref": 3,
                            "prefix.resume_pin": 1},
        },
    }
    registry = CollectorRegistry()
    register_engine_lifecycle(lambda: stats, registry=registry, key="m1")

    def val(name, **labels):
        return registry.get_sample_value(name, {"model": "m1", **labels})

    assert val("engine_ledger_outstanding", resource="pages.ref") == 3
    assert val("engine_ledger_outstanding", resource="prefix.resume_pin") == 1
    assert val("engine_ledger_outstanding", resource="pages.slot") == 0
    assert val("engine_ledger_leaks_total") == 2
    # unarmed providers (ledger None) export no ledger families
    registry2 = CollectorRegistry()
    register_engine_lifecycle(
        lambda: {"queue_depth": 1, "ledger": None},
        registry=registry2, key="m2",
    )
    assert registry2.get_sample_value(
        "engine_ledger_leaks_total", {"model": "m2"}
    ) is None

    # end to end against a REAL engine with the ledger armed: the engine's
    # lifecycle_stats carries the live block, and a pool acquire in the
    # process surfaces in the outstanding gauge
    import jax

    from clearml_serving_tpu import models
    from clearml_serving_tpu.llm import lifecycle_ledger
    from clearml_serving_tpu.llm.engine import LLMEngineCore

    monkeypatch.setenv("TPUSERVE_LEDGER", "1")
    bundle = models.build_model(
        "llama", {"preset": "llama-tiny", "dtype": "float32"}
    )
    params = bundle.init(jax.random.PRNGKey(0))
    engine = LLMEngineCore(
        bundle, params, max_batch=2, max_seq_len=64, cache_mode="paged",
        page_size=16, prefill_buckets=[16], eos_token_id=None,
    )
    try:
        assert engine._ledger is not None
        engine._ledger.reset(strict=False)
        registry3 = CollectorRegistry()
        register_engine_lifecycle(
            engine.lifecycle_stats, registry=registry3, key="llm"
        )
        engine.paged_cache.pool.allocate(0, 20)  # 2 pages outstanding
        assert registry3.get_sample_value(
            "engine_ledger_outstanding",
            {"model": "llm", "resource": "pages.slot"},
        ) == 2
        assert registry3.get_sample_value(
            "engine_ledger_leaks_total", {"model": "llm"}
        ) == 0
        engine.paged_cache.pool.free(0)
        assert registry3.get_sample_value(
            "engine_ledger_outstanding",
            {"model": "llm", "resource": "pages.slot"},
        ) == 0
    finally:
        engine.stop()
        lifecycle_ledger.get().reset(strict=False)
        lifecycle_ledger.disarm()


def test_replica_label_on_lifecycle_families():
    """Replica fleets (docs/replication.md): a provider that reports a
    ``replica`` id gets the replica label on ITS samples (two replicas of
    one model would otherwise emit duplicate series and Prometheus
    rejects the scrape), a ``model`` key overrides the entry key so entry
    keys stay unique per replica — and the label shape is PER PROVIDER: a
    fleet registering on a shared registry never changes a legacy
    single-engine endpoint's series identity."""
    from clearml_serving_tpu.statistics.metrics import register_engine_lifecycle

    s0 = {
        "model": "m", "replica": "r0",
        "queue_depth": 2, "active_slots": 1, "ready": 1,
    }
    s1 = {
        "model": "m", "replica": "r1",
        "queue_depth": 5, "active_slots": 0, "ready": 0,
        "sheds": {"queue": 3},
        "watchdog_trips": 1,
    }
    registry = CollectorRegistry()
    register_engine_lifecycle(lambda: s0, registry=registry, key="m@r0")
    register_engine_lifecycle(lambda: s1, registry=registry, key="m@r1")
    # a LEGACY endpoint co-hosted on the same registry
    register_engine_lifecycle(
        lambda: {"queue_depth": 1, "ready": 1}, registry=registry,
        key="legacy",
    )

    def val(name, **labels):
        return registry.get_sample_value(name, labels)

    assert val("engine_queue_depth", model="m", replica="r0",
               **{"class": "all"}) == 2
    assert val("engine_queue_depth", model="m", replica="r1",
               **{"class": "all"}) == 5
    assert val("engine_ready", model="m", replica="r0") == 1
    assert val("engine_ready", model="m", replica="r1") == 0
    assert val("engine_sheds_total", model="m", replica="r1",
               reason="queue", **{"class": "all"}) == 3
    assert val("engine_watchdog_trips_total", model="m", replica="r1") == 1
    # the legacy endpoint's series identity is UNTOUCHED by the fleet:
    # dashboards matching {model="legacy"} with no replica label keep
    # working, and nothing flaps when the fleet endpoint is evicted
    assert val("engine_queue_depth", model="legacy",
               **{"class": "all"}) == 1
    assert val("engine_ready", model="legacy") == 1
    # gauges read live on the next scrape
    s0["queue_depth"] = 7
    assert val("engine_queue_depth", model="m", replica="r0",
               **{"class": "all"}) == 7


def test_replica_router_collector_exports_ring_and_routes():
    """router_requests_total{replica,route} + router_ring_size and the
    eject/readmit/fleet-brownout families from a synthetic
    ReplicaRouter.stats() provider (docs/replication.md)."""
    from clearml_serving_tpu.statistics.metrics import register_replica_router

    stats = {
        "replicas": 2,
        "ring_size": 1,
        "requests": {
            "r0": {"affine": 5, "spill": 1, "rebalance": 2},
            "r1": {"affine": 3, "spill": 0, "rebalance": 0},
        },
        "ejections": {"r0": 0, "r1": 1},
        "readmissions": {"r0": 0, "r1": 1},
        "fleet_sheds": {"best_effort": 4},
        "fleet_brownout": {"stage": 2, "stages": {"r0": 2, "r1": 3}},
    }
    registry = CollectorRegistry()
    register_replica_router(lambda: stats, registry=registry, key="m1")

    def val(name, **labels):
        return registry.get_sample_value(name, {"model": "m1", **labels})

    assert val("router_ring_size") == 1
    assert val("router_replicas") == 2
    # providers without a roles map default every member to role="hybrid"
    assert val("router_requests_total", replica="r0", route="affine",
               role="hybrid") == 5
    assert val("router_requests_total", replica="r0", route="spill",
               role="hybrid") == 1
    assert val("router_requests_total", replica="r1", route="rebalance",
               role="hybrid") == 0
    assert val("router_ejections_total", replica="r1", role="hybrid") == 1
    assert val("router_readmissions_total", replica="r1",
               role="hybrid") == 1
    assert val("router_fleet_brownout_stage") == 2
    assert val("router_fleet_sheds_total", **{"class": "best_effort"}) == 4
    # the ring gauge reads live on the next scrape
    stats["ring_size"] = 2
    assert val("router_ring_size") == 2


def test_replica_router_role_label_and_role_members():
    """Role-split fleets (docs/disaggregation.md): the per-replica
    router families carry the replica's role, and router_role_members
    gauges the ring composition by role."""
    from clearml_serving_tpu.statistics.metrics import register_replica_router

    stats = {
        "replicas": 2,
        "ring_size": 2,
        "ring": ["r0", "r1"],
        "roles": {"r0": "prefill", "r1": "decode"},
        "requests": {
            "r0": {"affine": 1, "spill": 0, "rebalance": 0},
            "r1": {"affine": 7, "spill": 0, "rebalance": 1},
        },
        "ejections": {"r0": 2, "r1": 0},
        "readmissions": {"r0": 2, "r1": 0},
        "fleet_sheds": {"best_effort": 0},
        "fleet_brownout": {"stage": 0, "stages": {"r0": 0, "r1": 0}},
    }
    registry = CollectorRegistry()
    register_replica_router(lambda: stats, registry=registry, key="m1")

    def val(name, **labels):
        return registry.get_sample_value(name, {"model": "m1", **labels})

    assert val("router_requests_total", replica="r1", route="affine",
               role="decode") == 7
    assert val("router_requests_total", replica="r0", route="affine",
               role="prefill") == 1
    assert val("router_ejections_total", replica="r0", role="prefill") == 2
    assert val("router_role_members", role="prefill") == 1
    assert val("router_role_members", role="decode") == 1
    # a member leaving the ring moves the role gauge on the next scrape
    stats["ring"] = ["r1"]
    assert val("router_role_members", role="prefill") == 0


def test_engine_kv_ship_metrics_exported():
    """engine_kv_ship_pages_total{direction} / engine_kv_ship_ms /
    engine_kv_ship_hit_rate from a synthetic lifecycle provider carrying
    the kv_ship block (docs/disaggregation.md)."""
    from clearml_serving_tpu.statistics.metrics import (
        register_engine_lifecycle,
    )

    stats = {
        "model": "m1",
        "replica": "r1",
        "queue_depth": 0,
        "active_slots": 0,
        "ready": 1,
        "kv_ship": {
            "role": "decode",
            "ships": 0, "ship_pages": 0, "ship_drops": 0,
            "receives": 4, "receive_pages": 9,
            "receive_empty": 1, "receive_failures": 0,
            "hits": 4, "recomputes": 1, "hit_rate": 0.8,
            "ship_ms": {"buckets": [1, 5], "counts": [0, 0, 0],
                        "sum_ms": 0.0},
            "receive_ms": {"buckets": [1, 5], "counts": [2, 1, 1],
                           "sum_ms": 12.5},
        },
    }
    registry = CollectorRegistry()
    register_engine_lifecycle(lambda: stats, registry=registry, key="m1")

    def val(name, **labels):
        return registry.get_sample_value(
            name, {"model": "m1", "replica": "r1", **labels}
        )

    assert val("engine_kv_ship_pages_total", direction="out") == 0
    assert val("engine_kv_ship_pages_total", direction="in") == 9
    assert val("engine_kv_ship_hit_rate") == 0.8
    assert val("engine_kv_ship_ms_count", direction="in") == 4
    assert val("engine_kv_ship_ms_sum", direction="in") == 12.5
    # counters move on the next scrape
    stats["kv_ship"]["receive_pages"] = 12
    assert val("engine_kv_ship_pages_total", direction="in") == 12


def test_disagg_fleet_real_engine_end_to_end():
    """End to end against a REAL prefill/decode-split group: the decode
    replica's lifecycle provider exports the ship families after a
    disaggregated request actually shipped (docs/disaggregation.md)."""
    import asyncio

    import jax

    from clearml_serving_tpu import models
    from clearml_serving_tpu.llm.engine import GenRequest, LLMEngineCore
    from clearml_serving_tpu.llm.replica import ReplicaGroup
    from clearml_serving_tpu.statistics.metrics import (
        register_engine_lifecycle,
        register_replica_router,
    )

    bundle = models.build_model(
        "llama", {"preset": "llama-tiny", "dtype": "float32"}
    )
    params = bundle.init(jax.random.PRNGKey(0))
    engines = [
        LLMEngineCore(
            bundle, params, replica="r{}".format(i), max_batch=2,
            max_seq_len=128, prefill_buckets=[32, 64], eos_token_id=None,
            cache_mode="paged", page_size=16, prefix_cache=64,
            prefix_block=16, num_pages=65,
        )
        for i in range(2)
    ]
    group = ReplicaGroup(engines, roles=["prefill", "decode"])
    try:
        registry = CollectorRegistry()
        for replica in group.replicas:

            def provider(engine=replica.engine):
                s = engine.lifecycle_stats()
                s["model"] = "fleet"
                return s

            register_engine_lifecycle(
                provider, registry=registry, key="fleet@" + replica.name
            )
        register_replica_router(
            lambda: dict(group.router.stats(), model="fleet"),
            registry=registry, key="fleet",
        )

        async def run():
            conv = [(5 + i * 3) % 90 + 1 for i in range(40)]
            request = GenRequest(prompt_ids=conv, max_new_tokens=2)
            async for _ in group.generate(request):
                pass
            await group.wait_drained()

        asyncio.run(run())

        def val(name, **labels):
            return registry.get_sample_value(
                name, {"model": "fleet", **labels}
            )

        assert val("engine_kv_ship_pages_total", replica="r0",
                   direction="out") >= 1
        assert val("engine_kv_ship_pages_total", replica="r1",
                   direction="in") >= 1
        assert val("engine_kv_ship_hit_rate", replica="r1") == 1.0
        assert val("router_role_members", role="decode") == 1
        assert val("router_role_members", role="prefill") == 1
        assert val("router_requests_total", replica="r1", route="affine",
                   role="decode") == 1
    finally:
        group.stop()


def test_replica_fleet_real_engine_end_to_end():
    """End to end against a REAL 2-replica group: per-replica lifecycle
    providers (replica label from the engine's own lifecycle_stats) and
    the router provider feed one registry, exactly as openai_api wires
    them."""
    import asyncio

    import jax

    from clearml_serving_tpu import models
    from clearml_serving_tpu.llm.engine import GenRequest, LLMEngineCore
    from clearml_serving_tpu.llm.replica import ReplicaGroup
    from clearml_serving_tpu.statistics.metrics import (
        register_engine_lifecycle,
        register_replica_router,
    )

    bundle = models.build_model(
        "llama", {"preset": "llama-tiny", "dtype": "float32"}
    )
    params = bundle.init(jax.random.PRNGKey(0))
    engines = [
        LLMEngineCore(
            bundle, params, replica="r{}".format(i), max_batch=2,
            max_seq_len=64,
            prefill_buckets=[32], eos_token_id=None, cache_mode="paged",
            page_size=16, prefix_cache=32, prefix_block=16,
        )
        for i in range(2)
    ]
    group = ReplicaGroup(engines)
    try:
        registry = CollectorRegistry()
        for replica in group.replicas:

            def provider(engine=replica.engine):
                s = engine.lifecycle_stats()
                s["model"] = "fleet"
                return s

            register_engine_lifecycle(
                provider, registry=registry, key="fleet@" + replica.name
            )
        register_replica_router(
            lambda: dict(group.router.stats(), model="fleet"),
            registry=registry, key="fleet",
        )

        async def run():
            conv = [(5 + i * 3) % 90 + 1 for i in range(40)]
            for turn in range(2):
                request = GenRequest(
                    prompt_ids=conv + [7] * (turn + 1), max_new_tokens=2
                )
                async for _ in group.generate(request):
                    pass
            await group.wait_drained()
            return request._replica_name

        home = asyncio.run(run())

        def val(name, **labels):
            return registry.get_sample_value(name, {"model": "fleet", **labels})

        assert val("engine_ready", replica="r0") == 1
        assert val("engine_ready", replica="r1") == 1
        assert val("router_ring_size") == 2
        home_id = home  # "r0"/"r1"
        assert val("router_requests_total", replica=home_id,
                   route="affine", role="hybrid") == 2
    finally:
        group.stop()


def test_prune_entries_drops_stale_replica_keys():
    """Endpoint hot-reloads that change the replica count must not leave
    stale per-replica collector entries (docs/replication.md): a fleet
    scaled down (or reloaded as a single engine) prunes its model@rN
    entries — nothing pins dead engines' caches or exports frozen
    series — while OTHER endpoints' entries are untouched."""
    from clearml_serving_tpu.statistics.metrics import (
        prune_engine_lifecycle,
        register_engine_lifecycle,
    )

    registry = CollectorRegistry()
    for key in ("m@r0", "m@r1", "m@r2", "m", "m2@r0", "m2"):
        register_engine_lifecycle(
            lambda key=key: {"queue_depth": 1}, registry=registry, key=key
        )
    # reload to 2 replicas: bare "m" and "m@r2" go, r0/r1 stay, m2* stays
    prune_engine_lifecycle("m", {"m@r0", "m@r1"}, registry=registry)

    def has(key):
        label = {"model": key, "class": "all"}
        return registry.get_sample_value("engine_queue_depth", label) is not None

    assert has("m@r0") and has("m@r1")
    assert not has("m@r2") and not has("m")
    assert has("m2@r0") and has("m2")
    # reload to a single engine: every m@rN goes
    register_engine_lifecycle(
        lambda: {"queue_depth": 3}, registry=registry, key="m"
    )
    prune_engine_lifecycle("m", {"m"}, registry=registry)
    assert has("m") and not has("m@r0") and not has("m@r1")


def test_prefix_cache_collector_replica_label_split():
    """Fleet prefix-cache entries carry the {model, replica} label split
    (docs/replication.md) — never a mangled model label — while legacy
    entries on the same collector keep the historical {model} shape."""
    from clearml_serving_tpu.llm.kv_cache import PagePool
    from clearml_serving_tpu.llm.prefix_cache import RadixPrefixCache
    from clearml_serving_tpu.statistics.metrics import register_prefix_cache

    registry = CollectorRegistry()
    pool = PagePool(num_pages=16, page_size=2, max_slots=2)
    cache_r0 = RadixPrefixCache(block=4, pool=pool, page_bytes=32)
    cache_r1 = RadixPrefixCache(block=4)
    legacy = RadixPrefixCache(block=4)
    register_prefix_cache(cache_r0, pool, registry=registry,
                          key="fleet@r0", model="fleet", replica="r0")
    register_prefix_cache(cache_r1, registry=registry,
                          key="fleet@r1", model="fleet", replica="r1")
    register_prefix_cache(legacy, registry=registry, key="plain")

    cache_r0.lookup_pages([1, 2, 3, 4, 5, 6], 0)  # miss
    legacy.lookup([9, 9, 9, 9, 9], 0)             # miss

    def val(name, **labels):
        return registry.get_sample_value(name, labels)

    # fleet rows: real model label + replica label (joinable with the
    # lifecycle/router families on (model, replica))
    assert val("llm_prefix_cache_misses_total",
               model="fleet", replica="r0") == 1
    assert val("llm_prefix_cache_misses_total",
               model="fleet", replica="r1") == 0
    assert val("kv_pool_free_pages", model="fleet", replica="r0") is not None
    # no mangled model label anywhere
    assert val("llm_prefix_cache_misses_total", model="fleet@r0") is None
    # the legacy entry's series identity is untouched
    assert val("llm_prefix_cache_misses_total", model="plain") == 1


def test_engine_kv_wire_metrics_exported():
    """engine_kv_ship_wire_bytes_total{direction} + engine_kv_ship_rtt_ms
    from a synthetic lifecycle provider whose kv_ship block carries the
    socket transport's wire sub-block (llm/kv_wire.py); providers on the
    in-heap backend (no wire block) must not emit the families at all."""
    from clearml_serving_tpu.statistics.metrics import (
        register_engine_lifecycle,
    )

    stats = {
        "model": "m1",
        "replica": "r1",
        "queue_depth": 0,
        "active_slots": 0,
        "ready": 1,
        "kv_ship": {
            "role": "decode",
            "ships": 1, "ship_pages": 2, "ship_drops": 0,
            "receives": 1, "receive_pages": 2,
            "receive_empty": 0, "receive_failures": 0,
            "hits": 1, "recomputes": 0, "hit_rate": 1.0,
            "ship_ms": {"buckets": [1, 5], "counts": [1, 0, 0],
                        "sum_ms": 0.5},
            "receive_ms": {"buckets": [1, 5], "counts": [1, 0, 0],
                           "sum_ms": 0.5},
            "transport": {
                "backend": "socket_slab",
                "wire": {
                    "bytes_sent": 4096, "bytes_received": 1024,
                    "frames_sent": 2, "frames_received": 1,
                    "send_failures": 0, "recv_failures": 0,
                    "rtt_ms": {"buckets": [1.0, 5.0],
                               "counts": [1, 1, 0], "sum_ms": 3.5,
                               "count": 2},
                },
            },
        },
    }
    registry = CollectorRegistry()
    register_engine_lifecycle(lambda: stats, registry=registry, key="m1")

    def val(name, **labels):
        return registry.get_sample_value(
            name, {"model": "m1", "replica": "r1", **labels}
        )

    assert val("engine_kv_ship_wire_bytes_total", direction="out") == 4096
    assert val("engine_kv_ship_wire_bytes_total", direction="in") == 1024
    assert val("engine_kv_ship_rtt_ms_count") == 2
    assert val("engine_kv_ship_rtt_ms_sum") == 3.5
    assert val("engine_kv_ship_rtt_ms_bucket", le="1.0") == 1
    assert val("engine_kv_ship_rtt_ms_bucket", le="5.0") == 2
    assert val("engine_kv_ship_rtt_ms_bucket", le="+Inf") == 2
    # counters move on the next scrape
    stats["kv_ship"]["transport"]["wire"]["bytes_sent"] = 8192
    assert val("engine_kv_ship_wire_bytes_total", direction="out") == 8192
    # a shared-slab provider (no wire block) does not emit the families
    registry2 = CollectorRegistry()
    shared = dict(stats)
    shared["kv_ship"] = dict(stats["kv_ship"], transport={"backend": "shared_slab"})
    register_engine_lifecycle(lambda: shared, registry=registry2, key="m1")
    assert registry2.get_sample_value(
        "engine_kv_ship_wire_bytes_total",
        {"model": "m1", "replica": "r1", "direction": "out"},
    ) is None


def test_router_replica_backend_info_gauge():
    """router_replica_backend{model,backend} = 1: the info-style gauge a
    dashboard joins on to tell process fleets from in-process ones
    (docs/replication.md)."""
    from clearml_serving_tpu.statistics.metrics import register_replica_router

    stats = {
        "replicas": 2,
        "ring_size": 2,
        "replica_backend": "process",
        "requests": {},
    }
    registry = CollectorRegistry()
    register_replica_router(lambda: stats, registry=registry, key="m1")
    assert registry.get_sample_value(
        "router_replica_backend", {"model": "m1", "backend": "process"}
    ) == 1
    assert registry.get_sample_value(
        "router_replica_backend", {"model": "m1", "backend": "inprocess"}
    ) is None
    # live: a (hypothetical) backend change moves the label on next scrape
    stats["replica_backend"] = "inprocess"
    assert registry.get_sample_value(
        "router_replica_backend", {"model": "m1", "backend": "inprocess"}
    ) == 1


@pytest.mark.slow
def test_socket_fleet_wire_metrics_end_to_end():
    """End to end against a REAL prefill/decode group on the SOCKET
    transport backend: after a disaggregated request ships over the wire,
    the prefill replica exports wire bytes out + an RTT sample, the
    decode replica exports wire bytes in, and the router carries the
    backend info gauge."""
    import asyncio

    import jax

    from clearml_serving_tpu import models
    from clearml_serving_tpu.llm.engine import GenRequest, LLMEngineCore
    from clearml_serving_tpu.llm.replica import ReplicaGroup
    from clearml_serving_tpu.statistics.metrics import (
        register_engine_lifecycle,
        register_replica_router,
    )

    bundle = models.build_model(
        "llama", {"preset": "llama-tiny", "dtype": "float32"}
    )
    params = bundle.init(jax.random.PRNGKey(0))
    engines = [
        LLMEngineCore(
            bundle, params, replica="r{}".format(i), max_batch=2,
            max_seq_len=128, prefill_buckets=[32, 64], eos_token_id=None,
            cache_mode="paged", page_size=16, prefix_cache=64,
            prefix_block=16, num_pages=65,
        )
        for i in range(2)
    ]
    group = ReplicaGroup(
        engines, roles=["prefill", "decode"], kv_transport_backend="socket"
    )
    try:
        registry = CollectorRegistry()
        for replica in group.replicas:

            def provider(engine=replica.engine):
                s = engine.lifecycle_stats()
                s["model"] = "fleet"
                return s

            register_engine_lifecycle(
                provider, registry=registry, key="fleet@" + replica.name
            )
        register_replica_router(
            lambda: dict(group.router.stats(), model="fleet"),
            registry=registry, key="fleet",
        )

        async def run():
            conv = [(5 + i * 3) % 90 + 1 for i in range(40)]
            request = GenRequest(prompt_ids=conv, max_new_tokens=2)
            async for _ in group.generate(request):
                pass
            await group.wait_drained()

        asyncio.run(run())

        def val(name, **labels):
            return registry.get_sample_value(
                name, {"model": "fleet", **labels}
            )

        assert val("engine_kv_ship_wire_bytes_total", replica="r0",
                   direction="out") > 0
        assert val("engine_kv_ship_rtt_ms_count", replica="r0") >= 1
        assert val("engine_kv_ship_wire_bytes_total", replica="r1",
                   direction="in") > 0
        assert val("engine_kv_ship_hit_rate", replica="r1") == 1.0
        assert val("router_replica_backend", backend="inprocess") == 1
    finally:
        group.stop()


def test_engine_spec_tree_and_draft_ahead_metrics_exported():
    """Tree-draft + draft-ahead observability (docs/spec_decode_trees.md):
    engine_spec_tree_accept_depth histogram,
    engine_spec_proposer_hits_total{proposer} counter and the
    engine_kv_ship_overlap_ratio gauge — from a synthetic lifecycle
    provider AND end to end against a real tree-spec engine."""
    from clearml_serving_tpu.statistics.metrics import register_engine_lifecycle

    stats = {
        "queue_depth": 0,
        "ragged": {
            "step_token_budget": 16,
            "effective_budget": 16,
            "prefill_jobs": 0,
            "steps": 3,
            "step_rows": {"spec_verify": 3},
            "spec_tree_depth": {
                "buckets": [0, 1, 2, 3, 4],
                "counts": [1, 0, 2, 1, 0, 0],
                "sum_ms": 7.0,
                "count": 4,
            },
            "spec_tree_fallbacks": 0,
            "spec_proposer": {
                "name": "ngram-forest", "proposed": 9, "hit": 6,
                "branched": 4,
            },
        },
        "kv_ship": {
            "ships": 2, "ship_pages": 8, "ship_drops": 0,
            "draft_ships": 3, "draft_pages": 6, "draft_aborts": 0,
            "overlap_ratio": 0.75,
        },
    }
    registry = CollectorRegistry()
    register_engine_lifecycle(lambda: stats, registry=registry, key="m1")

    def val(name, **labels):
        return registry.get_sample_value(name, {"model": "m1", **labels})

    # accepted-depth histogram: cumulative buckets + count/sum
    assert val("engine_spec_tree_accept_depth_count") == 4
    assert val("engine_spec_tree_accept_depth_sum") == 7.0
    assert val("engine_spec_tree_accept_depth_bucket", le="2") == 3
    assert val("engine_spec_tree_accept_depth_bucket", le="+Inf") == 4
    # proposer hits carry the backend label
    assert val(
        "engine_spec_proposer_hits_total", proposer="ngram-forest"
    ) == 6
    # draft-ahead overlap: shipped-before-commit / all shipped pages
    assert val("engine_kv_ship_overlap_ratio") == 0.75

    # chain / non-tree providers (spec_tree_depth None, no proposer dict)
    # skip the tree families without breaking the ragged block
    registry2 = CollectorRegistry()
    register_engine_lifecycle(
        lambda: {
            "queue_depth": 0,
            "ragged": {"spec_tree_depth": None, "spec_proposer": None,
                       "step_rows": {"decode": 2}},
        },
        registry=registry2, key="m2",
    )
    assert registry2.get_sample_value(
        "engine_spec_tree_accept_depth_count", {"model": "m2"}
    ) is None
    assert registry2.get_sample_value(
        "engine_step_rows_total", {"model": "m2", "phase": "decode"}
    ) == 2

    # end to end: a real tree-spec engine feeds the same families
    import asyncio

    import jax

    from clearml_serving_tpu import models
    from clearml_serving_tpu.llm.engine import GenRequest, LLMEngineCore

    bundle = models.build_model(
        "llama", {"preset": "llama-tiny", "dtype": "float32"}
    )
    params = bundle.init(jax.random.PRNGKey(0))
    engine = LLMEngineCore(
        bundle, params, max_batch=2, max_seq_len=64, prefill_buckets=[16],
        eos_token_id=None, scheduler="ragged", step_token_budget=12,
        cache_mode="paged", speculation="ngram", spec_k=4, spec_ngram=2,
        spec_tree=True, spec_branch=2,
    )
    try:
        registry3 = CollectorRegistry()
        register_engine_lifecycle(
            engine.lifecycle_stats, registry=registry3, key="llm"
        )

        async def run():
            req = GenRequest(
                prompt_ids=[5, 9, 2, 17, 5, 9, 2], max_new_tokens=8
            )
            out = [t async for t in engine.generate(req)]
            await engine.wait_drained()
            return out

        out = asyncio.run(run())
        assert len(out) == 8

        def rval(name, **labels):
            return registry3.get_sample_value(
                name, {"model": "llm", **labels}
            )

        assert rval("engine_step_rows_total", phase="spec_verify") >= 1
        assert rval("engine_spec_tree_accept_depth_count") >= 1
        assert rval(
            "engine_spec_proposer_hits_total", proposer="ngram-forest"
        ) is not None
    finally:
        engine.stop()
