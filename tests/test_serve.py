"""Serve layer tests (reference strategy: serve/tests/ unit + e2e suites,
e.g. test_deploy.py, test_handle.py, test_batching.py, test_proxy.py)."""
import json
import time
import urllib.request

import pytest

import ray_tpu
from ray_tpu import serve
from ray_tpu.serve.config import AutoscalingConfig


@pytest.fixture(scope="module", autouse=True)
def _cluster():
    ray_tpu.init(num_cpus=8, ignore_reinit_error=True)
    yield
    serve.shutdown()
    ray_tpu.shutdown()


@pytest.fixture(autouse=True)
def _clean_apps():
    yield
    # Delete apps between tests but keep controller/proxy warm.
    try:
        for app in {i.get("app") for i in serve.status().values()}:
            if app:
                serve.delete(app)
    except Exception:
        pass


def test_function_deployment():
    @serve.deployment
    def double(x):
        return x * 2

    handle = serve.run(double.bind(), name="fn_app", route_prefix="/double")
    assert handle.remote(21).result(timeout_s=30) == 42


def test_class_deployment_multiple_replicas():
    @serve.deployment(num_replicas=3)
    class Counter:
        def __init__(self, base):
            self.base = base

        def __call__(self, x):
            return self.base + x

        def which(self):
            import os
            return os.getpid()

    handle = serve.run(Counter.bind(100), name="cls_app",
                       route_prefix="/counter")
    results = [handle.remote(i).result(timeout_s=30) for i in range(10)]
    assert results == [100 + i for i in range(10)]
    # Pow-2 routing should spread across >1 replica process.
    pids = {handle.which.remote().result(timeout_s=30) for _ in range(20)}
    assert len(pids) >= 2


def test_model_composition():
    @serve.deployment
    class Preprocessor:
        def __call__(self, x):
            return x + 1

    @serve.deployment
    class Model:
        def __init__(self, pre):
            self.pre = pre  # DeploymentHandle

        def __call__(self, x):
            y = self.pre.remote(x).result(timeout_s=30)
            return y * 10

    handle = serve.run(Model.bind(Preprocessor.bind()), name="comp_app",
                       route_prefix="/comp")
    assert handle.remote(4).result(timeout_s=30) == 50


def test_serve_batch():
    @serve.deployment
    class BatchModel:
        def __init__(self):
            self.batch_sizes = []

        @serve.batch(max_batch_size=4, batch_wait_timeout_s=0.05)
        async def __call__(self, items):
            self.batch_sizes.append(len(items))
            return [i * 2 for i in items]

        def seen(self):
            return self.batch_sizes

    handle = serve.run(BatchModel.bind(), name="batch_app",
                       route_prefix="/batch")
    responses = [handle.remote(i) for i in range(8)]
    assert [r.result(timeout_s=30) for r in responses] == [
        i * 2 for i in range(8)]
    sizes = handle.seen.remote().result(timeout_s=30)
    assert max(sizes) > 1  # actually batched


def test_http_proxy():
    @serve.deployment
    def ingress(request):
        return {"method": request["method"], "echo": request["body"]}

    serve.run(ingress.bind(), name="http_app", route_prefix="/api")
    addr = serve.proxy_address()
    assert addr is not None
    # health endpoint
    with urllib.request.urlopen(addr + "/-/healthz", timeout=10) as r:
        assert r.read() == b"success"
    req = urllib.request.Request(
        addr + "/api", data=json.dumps({"x": 5}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=30) as r:
        out = json.loads(r.read())
    assert out == {"method": "POST", "echo": {"x": 5}}


def test_user_config_reconfigure():
    @serve.deployment(user_config={"scale": 2})
    class Scaler:
        def __init__(self):
            self.scale = 1

        def reconfigure(self, cfg):
            self.scale = cfg["scale"]

        def __call__(self, x):
            return x * self.scale

    handle = serve.run(Scaler.bind(), name="cfg_app", route_prefix="/scale")
    assert handle.remote(3).result(timeout_s=30) == 6
    # In-place redeploy with new user_config (same code/args).
    serve.run(Scaler.options(user_config={"scale": 5}).bind(),
              name="cfg_app", route_prefix="/scale")
    deadline = time.time() + 15
    while time.time() < deadline:
        if handle.remote(3).result(timeout_s=30) == 15:
            break
        time.sleep(0.2)
    assert handle.remote(3).result(timeout_s=30) == 15


def test_status_and_delete():
    @serve.deployment(num_replicas=2)
    def noop(_):
        return "ok"

    serve.run(noop.bind(), name="del_app", route_prefix="/del")
    st = serve.status()
    assert "noop" in st and st["noop"]["target_replicas"] == 2
    serve.delete("del_app")
    assert "noop" not in serve.status()


def test_slow_constructor_is_starting_not_dead():
    """A replica whose constructor outlasts health_check_timeout_s (a
    model loading onto a chip) is STARTING: the controller must neither
    replace it nor leave replaced replicas alive holding resources."""
    from ray_tpu.util.state import list_actors

    @serve.deployment(health_check_timeout_s=0.5)
    class SlowInit:
        def __init__(self):
            time.sleep(3.0)  # > a health tick + the 0.5 s timeout

        def __call__(self, _):
            import os
            return os.getpid()

    handle = serve.run(SlowInit.bind(), name="slowinit_app",
                       route_prefix="/slowinit")
    pids = {handle.remote(i).result(timeout_s=60) for i in range(4)}
    time.sleep(2.1)  # one more health tick after the replica is ready
    pids.add(handle.remote(0).result(timeout_s=60))
    assert len(pids) == 1, f"replica was replaced while starting: {pids}"
    replicas = [a["name"] for a in list_actors()
                if (a.get("name") or "").startswith(
                    "SERVE_REPLICA::SlowInit#")
                and a.get("state") != "DEAD"]
    assert replicas == ["SERVE_REPLICA::SlowInit#1"], replicas


def test_autoscaling_policy_math():
    cfg = AutoscalingConfig(min_replicas=1, max_replicas=10,
                            target_ongoing_requests=2.0)
    assert cfg.desired_replicas(0.0, 4) == 1      # idle -> min
    assert cfg.desired_replicas(8.0, 2) == 4      # 8 ongoing / 2 per = 4
    assert cfg.desired_replicas(100.0, 4) == 10   # capped at max
    assert cfg.desired_replicas(0.0, 0) == 1


def test_autoscaling_e2e_upscale():
    @serve.deployment(autoscaling_config=AutoscalingConfig(
        min_replicas=1, max_replicas=3, target_ongoing_requests=1.0,
        upscale_delay_s=0.0, downscale_delay_s=60.0))
    class Slow:
        async def __call__(self, x):
            import asyncio
            await asyncio.sleep(12.0)
            return x

    handle = serve.run(Slow.bind(), name="auto_app", route_prefix="/slow")
    responses = [handle.remote(i) for i in range(6)]
    deadline = time.time() + 30
    scaled = False
    while time.time() < deadline:
        info = serve.status().get("Slow", {})
        if info.get("target_replicas", 1) > 1:
            scaled = True
            break
        time.sleep(0.5)
    assert scaled, f"no upscale happened: {serve.status()}"
    for r in responses:
        r.result(timeout_s=60)


class TestServeSchema:
    """Reference: serve/schema.py (ServeDeploySchema etc.) + serve
    deploy/build CLI."""

    def test_schema_validation(self):
        from ray_tpu.serve.schema import SchemaError, ServeDeploySchema
        import pytest as _pytest
        good = {"applications": [
            {"name": "a", "import_path": "m:app", "route_prefix": "/a"},
            {"name": "b", "import_path": "m:app2", "route_prefix": "/b"},
        ]}
        cfg = ServeDeploySchema.from_dict(good)
        assert [a.name for a in cfg.applications] == ["a", "b"]
        assert cfg.to_dict()["applications"][0]["import_path"] == "m:app"
        with _pytest.raises(SchemaError, match="duplicate application"):
            ServeDeploySchema.from_dict({"applications": [
                {"name": "x", "import_path": "m:a", "route_prefix": "/x"},
                {"name": "x", "import_path": "m:b", "route_prefix": "/y"}]})
        with _pytest.raises(SchemaError, match="route_prefix"):
            ServeDeploySchema.from_dict({"applications": [
                {"import_path": "m:a", "route_prefix": "no-slash"}]})
        with _pytest.raises(SchemaError, match="import_path"):
            ServeDeploySchema.from_dict({"applications": [{"name": "x"}]})
        with _pytest.raises(SchemaError, match="unknown deployment"):
            ServeDeploySchema.from_dict({"applications": [
                {"import_path": "m:a",
                 "deployments": [{"name": "D", "bogus_field": 1}]}]})

    def test_yaml_deploy_roundtrip(self, tmp_path):
        import yaml

        from ray_tpu import serve
        from ray_tpu.serve.schema import (ServeDeploySchema, build_config,
                                          deploy_config)
        cfg_path = tmp_path / "serve.yaml"
        cfg_path.write_text(yaml.safe_dump({"applications": [{
            "name": "yamlapp",
            "import_path": "tests.serve_test_app:app",
            "route_prefix": "/yaml",
            "deployments": [{"name": "EchoDeployment",
                             "num_replicas": 2}],
        }]}))
        schema = ServeDeploySchema.from_yaml(str(cfg_path))
        names = deploy_config(schema)
        assert names == ["yamlapp"]
        h = serve.get_app_handle("yamlapp")
        assert h.remote("hi").result(timeout_s=30) == "echo:hi"
        # the replica override took effect
        st = serve.status()
        echo = [v for k, v in st.items() if "EchoDeployment" in k]
        assert echo and echo[0]["target_replicas"] == 2
        # build emits a round-trippable config
        from tests.serve_test_app import app
        built = build_config(app, import_path="tests.serve_test_app:app")
        assert built["applications"][0]["deployments"][0][
            "name"] == "EchoDeployment"
        serve.delete("yamlapp")


class TestGrpcProxy:
    """Reference: the serve gRPC proxy alongside HTTP (proxy.py
    gRPCProxy); here a generic unary ingress + client."""

    def test_grpc_roundtrip_and_methods(self):
        from ray_tpu.serve._private.grpc_proxy import GrpcServeClient

        @serve.deployment
        class Calc:
            def __call__(self, x):
                return x * 2

            def add(self, a, b):
                return a + b

        serve.run(Calc.bind(), name="calc", route_prefix="/calc")
        proxy = serve.start_grpc(port=0)
        client = GrpcServeClient(f"127.0.0.1:{proxy.port}")
        try:
            assert client.call("calc", 21) == 42
            assert client.call("calc", 3, 4, method="add") == 7
            # concurrent calls through the pooled handler
            import concurrent.futures as cf
            with cf.ThreadPoolExecutor(8) as ex:
                outs = list(ex.map(lambda i: client.call("calc", i),
                                   range(16)))
            assert outs == [i * 2 for i in range(16)]
        finally:
            client.close()
            serve.delete("calc")

    def test_grpc_unknown_app_not_found(self):
        import grpc

        from ray_tpu.serve._private.grpc_proxy import GrpcServeClient
        proxy = serve.start_grpc(port=0)
        client = GrpcServeClient(f"127.0.0.1:{proxy.port}",
                                 timeout_s=10)
        try:
            with pytest.raises(grpc.RpcError) as e:
                client.call("nonexistent-app", 1)
            assert e.value.code() == grpc.StatusCode.NOT_FOUND
            # negative cache: an immediate retry is also NOT_FOUND and
            # does not re-query the controller within the TTL
            with pytest.raises(grpc.RpcError) as e2:
                client.call("nonexistent-app", 1)
            assert e2.value.code() == grpc.StatusCode.NOT_FOUND
        finally:
            client.close()

    def test_grpc_loopback_only_by_default(self):
        from ray_tpu.serve._private.grpc_proxy import GRPCProxy
        with pytest.raises(ValueError, match="loopback"):
            GRPCProxy(host="0.0.0.0")

    def test_grpc_redeploy_not_stale(self):
        """Regression: handle cache must expire so delete/redeploy
        routes to the new app within the TTL."""
        from ray_tpu.serve._private import grpc_proxy as gp
        from ray_tpu.serve._private.grpc_proxy import GrpcServeClient

        @serve.deployment
        class V1:
            def __call__(self, x):
                return f"v1:{x}"

        @serve.deployment
        class V2:
            def __call__(self, x):
                return f"v2:{x}"

        serve.run(V1.bind(), name="redeploy", route_prefix="/rd")
        proxy = serve.start_grpc(port=0)
        # Short client timeout: the first post-redeploy call may hit the
        # dying V1 replica; retries must fit the poll window.
        client = GrpcServeClient(f"127.0.0.1:{proxy.port}", timeout_s=3)
        try:
            assert client.call("redeploy", 1) == "v1:1"
            serve.delete("redeploy")
            serve.run(V2.bind(), name="redeploy", route_prefix="/rd")
            old_ttl = gp._HANDLE_TTL_S
            gp._HANDLE_TTL_S = 0.0  # expire immediately for the test
            try:
                import time as _t
                deadline = _t.monotonic() + 10
                out = None
                while _t.monotonic() < deadline:
                    try:
                        out = client.call("redeploy", 2)
                        if out == "v2:2":
                            break
                    except Exception:
                        pass
                    _t.sleep(0.2)
                assert out == "v2:2"
            finally:
                gp._HANDLE_TTL_S = old_ttl
        finally:
            client.close()
            serve.delete("redeploy")
