"""Serve operator surface: declarative deploy through the CLI
(`ray_tpu serve deploy/status/delete`) + typed protobuf servicers on the
gRPC proxy (reference: python/ray/serve/scripts.py `serve deploy`;
python/ray/serve/_private/proxy.py:558 gRPCProxy
grpc_servicer_functions)."""

import json

import pytest
import yaml

import ray_tpu
from ray_tpu import serve

pytestmark = pytest.mark.slow


RAY_START = dict(num_cpus=8, object_store_memory=128 * 1024 * 1024)


def test_typed_grpc_servicer(ray_start):
    """A hand-rolled protoc-shaped servicer registers on the proxy; rpc
    method names route to the deployment's same-named methods with typed
    payloads."""
    from ray_tpu.util.serve_grpc_demo import build_echo_app, echo_client

    serve.run(build_echo_app("svc"), name="typed", route_prefix=None)
    serve.start(
        grpc_port=0,
        grpc_servicer_functions=[
            "ray_tpu.util.serve_grpc_demo:add_EchoServicer_to_server"])
    addr = next(iter(serve.proxies().values()))["grpc"]
    assert echo_client(addr, "Echo", "hello", application="typed") \
        == "svc:hello"
    assert echo_client(addr, "Reverse", "abc", application="typed") \
        == "cba"
    serve.delete("typed")


def test_serve_cli_deploy_status_delete(ray_start, tmp_path):
    """serve deploy from YAML → status shows the app → delete removes
    it. The CLI runs in-process against the running cluster (the CLI
    functions are the product surface; process isolation is covered by
    the cluster-launcher tests)."""
    import io
    from contextlib import redirect_stdout

    from ray_tpu._private.worker import global_worker
    from ray_tpu.scripts import cli

    cfg = {
        "http_options": {"port": 18291},
        "applications": [{
            "name": "cliapp",
            "route_prefix": "/cliapp",
            "import_path": "ray_tpu.util.serve_grpc_demo:build_echo_app",
            "args": {"prefix": "cli"},
            "deployments": [{"name": "EchoDeployment",
                             "num_replicas": 2}],
        }],
    }
    cfg_path = tmp_path / "serve.yaml"
    cfg_path.write_text(yaml.safe_dump(cfg))

    class _A:
        config = str(cfg_path)
        address = global_worker.core.gcs_address

    out = io.StringIO()
    with redirect_stdout(out):
        cli.cmd_serve_deploy(_A())
    assert "deployed 1 application(s)" in out.getvalue()

    st = serve.status()
    assert st["cliapp"]["EchoDeployment"]["target"] == 2

    # HTTP ingress from the config's http_options
    import urllib.request
    req = urllib.request.Request(
        "http://127.0.0.1:18291/cliapp",
        data=json.dumps("ping").encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=60) as resp:
        body = json.loads(resp.read())
    assert body == {"echo": "ping", "prefix": "cli"}

    out = io.StringIO()
    with redirect_stdout(out):
        cli.cmd_serve_status(_A())
    parsed = json.loads(out.getvalue())
    assert "cliapp" in parsed["applications"]

    class _D:
        name = "cliapp"
        address = global_worker.core.gcs_address

    out = io.StringIO()
    with redirect_stdout(out):
        cli.cmd_serve_delete(_D())
    assert "cliapp" not in serve.status()
