"""Process-backend replica tests (serving/process_replica.py,
docs/replication.md "process backends").

Fast lane (tier-1): the EngineReplica surface pin (the router and group
drive both replica kinds through one duck-typed contract), the control
frame codec, request/error wire round-trips (remaining-budget deadline
convention, error-by-name reconstruction), and the guided-decoding named
rejection.

Slow lane (full suite): real 2-worker fleets — boot, stream, disagg
ship-over-socket, supervised restart after a REAL SIGKILL of the worker
(the process-backend variant of the PR 14 kill-prefill chaos case), and
teardown hygiene."""

import asyncio
import inspect
import os
import signal
import socket
import time

import pytest

from clearml_serving_tpu.errors import (
    DeadlineExceededError,
    EngineOverloadedError,
    EngineUnavailableError,
)
from clearml_serving_tpu.llm import faults
from clearml_serving_tpu.llm.replica import EngineReplica
from clearml_serving_tpu.serving.process_replica import (
    ProcessEngineReplica,
    _err_from_dict,
    _err_to_dict,
    _recv_frame_sock,
    _req_from_wire,
    _req_to_wire,
    _send_frame_sock,
    build_process_fleet,
)


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear()
    yield
    faults.clear()


# -- the shared replica surface ----------------------------------------------


def test_process_replica_pins_the_engine_replica_surface():
    """ProcessEngineReplica deliberately does NOT subclass EngineReplica
    (its worker bootstrap must not import the engine stack before device
    config) — this pin is what keeps the duck-typed contract honest: every
    public attribute the router/group consume exists on both."""
    for name, member in vars(EngineReplica).items():
        if name.startswith("_"):
            continue
        other = inspect.getattr_static(ProcessEngineReplica, name, None)
        assert other is not None, (
            "ProcessEngineReplica is missing EngineReplica surface "
            "member {!r}".format(name)
        )
        if isinstance(member, property):
            assert isinstance(other, property), (
                "{!r} is a property on EngineReplica but not on "
                "ProcessEngineReplica".format(name)
            )
        if inspect.iscoroutinefunction(member):
            assert inspect.iscoroutinefunction(other), (
                "{!r} is async on EngineReplica but not on "
                "ProcessEngineReplica".format(name)
            )


# -- frame codec --------------------------------------------------------------


def test_frame_codec_roundtrip_and_truncation():
    a, b = socket.socketpair()
    try:
        payload = {"id": 3, "op": "ping", "nested": {"x": [1, 2, 3]}}
        _send_frame_sock(a, payload)
        assert _recv_frame_sock(b) == payload
        # truncated frame: length prefix promises more than arrives
        a.sendall(b"\xff\x00\x00\x00{")
        a.close()
        assert _recv_frame_sock(b) is None
    finally:
        b.close()


# -- request wire --------------------------------------------------------------


def test_request_wire_roundtrip_carries_remaining_budgets():
    from clearml_serving_tpu.llm.engine import GenRequest

    request = GenRequest(
        prompt_ids=[1, 2, 3], max_new_tokens=7, temperature=0.5, top_k=11,
        seed=42, logprobs=2, logit_bias={5: -1.5}, stop_token_ids=[9],
        min_tokens=2, priority=1, total_timeout=30.0,
    )
    # a resolved monotonic deadline must cross as REMAINING time, not as
    # the other process's clock reading
    request._deadline = time.monotonic() + 10.0
    request._ship_to = "r1"
    request._shipped = True
    wire = _req_to_wire(request)
    assert 9.0 < wire["total_timeout"] <= 10.0
    assert wire["logit_bias"] == {"5": -1.5}
    rebuilt = _req_from_wire(wire)
    assert rebuilt.prompt_ids == [1, 2, 3]
    assert rebuilt.max_new_tokens == 7
    assert rebuilt.logit_bias == {5: -1.5}
    assert rebuilt.stop_token_ids == [9]
    assert rebuilt.seed == 42
    assert rebuilt._ship_to == "r1"
    # the post-ship marker drives the decode worker's hit/recompute
    # accounting (engine._count_ship_outcome) — it must survive the wire
    assert rebuilt._shipped is True


def test_guided_requests_rejected_with_named_error():
    from clearml_serving_tpu.llm.engine import GenRequest

    request = GenRequest(prompt_ids=[1], max_new_tokens=1)
    request.guided = {"choice": ["a", "b"]}
    with pytest.raises(ValueError, match="guided"):
        _req_to_wire(request)


# -- error wire ----------------------------------------------------------------


def test_error_wire_reconstructs_by_name_with_fields():
    err = _err_from_dict(_err_to_dict(
        EngineOverloadedError("queue full", retry_after=2.5, shed_class="bulk")
    ))
    assert isinstance(err, EngineOverloadedError)
    assert err.retry_after == 2.5 and err.shed_class == "bulk"

    err = _err_from_dict(_err_to_dict(DeadlineExceededError(
        "too slow", stage="ttft"
    )))
    assert isinstance(err, DeadlineExceededError) and err.stage == "ttft"

    assert isinstance(
        _err_from_dict(_err_to_dict(EngineUnavailableError("gone"))),
        EngineUnavailableError,
    )
    # builtins the degradation paths catch by type survive as builtins
    assert isinstance(_err_from_dict({"name": "MemoryError", "message": "x"}),
                      MemoryError)
    # unknown names degrade to RuntimeError, keeping the message
    err = _err_from_dict({"name": "WeirdVendorError", "message": "boom"})
    assert type(err) is RuntimeError and "boom" in str(err)


# -- real fleets (slow lane) ---------------------------------------------------


MODEL = {"arch": "llama", "config": {"preset": "llama-tiny"}, "seed": 0}
ENGINE = {
    "max_batch": 2, "max_seq_len": 64, "cache_mode": "paged",
    "page_size": 16, "num_pages": 64, "prefix_cache": True,
    "prefix_block": 16,
}


def _fleet(**kw):
    kwargs = dict(warmup_mode="off", cpu_devices=2, startup_timeout=180.0)
    kwargs.update(kw)
    return build_process_fleet(MODEL, dict(ENGINE), kw.pop("n", 2) or 2,
                               **kwargs)


async def _collect(group, ids, n=6, **kw):
    from clearml_serving_tpu.llm.engine import GenRequest

    request = GenRequest(prompt_ids=list(ids), max_new_tokens=n, **kw)
    out = []
    async for token in group.generate(request):
        out.append(int(token))
    return out


@pytest.mark.slow
def test_process_fleet_streams_match_inprocess_mono():
    """The 2-process fleet's greedy streams must be byte-identical to a
    monolithic in-process engine built from the same spec — the process
    boundary is a pure transport, never a numerics change."""
    import jax

    from clearml_serving_tpu import models
    from clearml_serving_tpu.llm.engine import LLMEngineCore

    prompts = [list(range(2, 22)), [7, 8, 9, 10]]
    seeded = dict(temperature=0.8, seed=1000)

    async def mono_arm():
        bundle = models.build_model("llama", {"preset": "llama-tiny"})
        params = bundle.init(jax.random.PRNGKey(0))
        engine = LLMEngineCore(bundle, params, **ENGINE)
        out = [await _collect(engine, ids) for ids in prompts]
        out.append(await _collect(engine, prompts[0], **seeded))
        await engine.wait_drained()
        engine.stop()
        return out

    expected = asyncio.run(mono_arm())
    group = _fleet()
    try:
        got = [asyncio.run(_collect(group, ids)) for ids in prompts]
        got.append(asyncio.run(_collect(group, prompts[0], **seeded)))
        assert got == expected
        health = group.health()
        blocks = health["replicas"]
        assert set(blocks) == {"r0", "r1"}
        for block in blocks.values():
            proc = block["process"]
            assert proc["backend"] == "process" and proc["alive"]
            assert proc["pid"] > 0 and proc["pid"] != os.getpid()
    finally:
        group.stop()


@pytest.mark.slow
def test_process_fleet_disagg_ships_kv_over_sockets(monkeypatch):
    """A prefill and a decode WORKER ship KV over the socket wire. The
    workers inherit the sentries from the environment, and the parent's
    only view of them is each worker's health block over the RPC: a clean
    sanitizer, no leak under the strict ledger, no implicit transfer."""
    monkeypatch.setenv("TPUSERVE_SANITIZE", "1")
    monkeypatch.setenv("TPUSERVE_LEDGER", "strict")
    monkeypatch.setenv("TPUSERVE_SHARD_SENTRY", "1")
    group = _fleet(roles=["prefill", "decode"])
    try:
        toks = asyncio.run(_collect(group, list(range(2, 34))))
        assert len(toks) == 6
        assert group.ship_legs >= 1 and group.ship_leg_failures == 0
        asyncio.run(group.wait_drained())
        blocks = [replica.engine.health() for replica in group.replicas]
        for block in blocks:
            assert block["sanitizer"]["checks"] > 0
            assert block["sanitizer"]["failures"] == 0
            assert block["ledger"]["strict"] and block["ledger"]["leaks"] == 0
            assert block["sharding"]["implicit_transfers"] == 0
        ship = blocks[1]["kv_ship"]
        assert ship["receives"] >= 1 and ship["hits"] >= 1
        assert ship["recomputes"] == 0
    finally:
        group.stop()


@pytest.mark.slow
def test_process_fleet_kill_worker_restarts_with_rewarm():
    """The process-backend variant of the PR 14 kill-prefill chaos case:
    the ``replica.proc.crash`` seam SIGKILLs the r0 worker FOR REAL;
    in-flight work fails over to the sibling, and the bounded
    restart-with-rewarm brings a fresh worker (new pid) back into the
    ring."""
    group = _fleet(heartbeat_interval=0.2, max_restarts=1)
    try:
        baseline = asyncio.run(_collect(group, [3, 4, 5, 6]))
        assert len(baseline) == 6
        replica = group.replicas[0]
        pid0 = replica.engine.pid
        assert pid0 and replica.engine.is_ready
        faults.configure([
            {"point": "replica.proc.crash", "action": "raise",
             "match_token": 0, "times": 1},
        ])
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline:
            if replica.restarts >= 1 and replica.engine.is_ready:
                break
            time.sleep(0.1)
        faults.clear()
        assert replica.restarts == 1, "worker was not restarted"
        assert replica.engine.pid != pid0, "restart must be a NEW process"
        # the reborn worker serves: route a stream pinned at it
        from clearml_serving_tpu.llm.engine import GenRequest

        async def pinned():
            request = GenRequest(prompt_ids=[11, 12, 13], max_new_tokens=4)
            request._replica_name = "r0"
            out = []
            async for token in group.generate(request):
                out.append(int(token))
            return out

        assert len(asyncio.run(pinned())) == 4
        # budget is bounded: a second kill (budget 1, already spent)
        # ejects the slot for good
        os.kill(replica.engine.pid, signal.SIGKILL)
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline:
            if not replica.engine.is_ready:
                break
            time.sleep(0.1)
        assert not replica.engine.is_ready
        # the fleet still serves on the surviving replica
        assert len(asyncio.run(_collect(group, [21, 22, 23]))) == 6
    finally:
        group.stop()


@pytest.mark.slow
def test_process_fleet_stop_reaps_every_worker():
    group = _fleet()
    pids = [r.engine.pid for r in group.replicas]
    assert all(pids)
    group.stop()
    deadline = time.monotonic() + 30.0
    while time.monotonic() < deadline:
        alive = []
        for pid in pids:
            try:
                os.kill(pid, 0)
            except ProcessLookupError:
                continue
            alive.append(pid)
        if not alive:
            break
        time.sleep(0.2)
    assert not alive, "worker pids survived group.stop(): {}".format(alive)
