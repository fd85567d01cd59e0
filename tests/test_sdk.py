"""SDK: decorators/config/graph discovery + a full `serve` supervisor run of
the aggregated graph (subprocess-per-service), hit over HTTP.

Mirrors the reference SDK tests + dynamo serve flow (reference: deploy/dynamo/
sdk/src/dynamo/sdk/tests/, cli/serving.py)."""

import json
import socket
import subprocess
import sys
import time
import urllib.request

import pytest

from dynamo_tpu.sdk.config import ServiceConfig
from dynamo_tpu.sdk.decorators import async_on_start, endpoint, service
from dynamo_tpu.sdk.dependency import depends
from dynamo_tpu.sdk.serve import discover_graph


def test_decorators_and_graph_discovery():
    @service(namespace="t", component="a")
    class A:
        @endpoint
        async def gen(self, req):
            yield req

        @async_on_start
        async def boot(self):
            pass

    @service(namespace="t", component="b")
    class B:
        a = depends(A)

    @service(namespace="t", component="c")
    class C:
        b = depends(B)
        a = depends(A)

    assert A.__dynamo_service__.component == "a"
    assert "gen" in A.__dynamo_endpoints__
    assert A.__dynamo_on_start__ == ["boot"]
    assert discover_graph(C) == [A, B, C]

    # subclass keeps inherited endpoints/hooks and can override depends
    @service(namespace="t", component="a2")
    class A2(A):
        pass

    assert "gen" in A2.__dynamo_endpoints__
    assert A2.__dynamo_on_start__ == ["boot"]


def test_service_config_layers(tmp_path):
    yaml_file = tmp_path / "conf.yaml"
    yaml_file.write_text("Worker:\n  model: llama\n  port: 8000\n")
    data = ServiceConfig.from_yaml_and_overrides(
        str(yaml_file), ["--Worker.port=9000", "--Frontend.host=0.0.0.0"]
    )
    assert data["Worker"]["model"] == "llama"
    assert data["Worker"]["port"] == 9000
    assert data["Frontend"]["host"] == "0.0.0.0"
    with pytest.raises(ValueError):
        ServiceConfig.from_yaml_and_overrides(None, ["badoverride"])


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


@pytest.mark.slow
def test_serve_supervisor_agg_graph(tmp_path):
    http_port = _free_port()
    cplane_port = _free_port()
    conf = tmp_path / "agg.yaml"
    conf.write_text(
        f"Frontend:\n  model: tiny\n  host: 127.0.0.1\n  port: {http_port}\n"
        "Processor:\n  routing: kv\n  kv_block_size: 4\n"
        # no chip here: run the chip-requesting worker on the CPU by name
        "TpuWorker:\n  model: tiny\n  resources:\n    tpu: 0\n"
    )
    proc = subprocess.Popen(
        [
            sys.executable, "-m", "dynamo_tpu.sdk.serve",
            "examples.graphs.agg:Frontend",
            "-f", str(conf),
            "--cplane", f"127.0.0.1:{cplane_port}",
            "--no-restart",
        ],
        cwd="/root/repo",
    )
    try:
        body = json.dumps(
            {
                "model": "tiny",
                "messages": [{"role": "user", "content": "hello graph"}],
                "max_tokens": 4,
                "temperature": 0,
            }
        ).encode()
        deadline = time.time() + 120
        last_err = None
        while time.time() < deadline:
            if proc.poll() is not None:
                pytest.fail(f"supervisor died rc={proc.returncode}")
            try:
                req = urllib.request.Request(
                    f"http://127.0.0.1:{http_port}/v1/chat/completions",
                    data=body,
                    headers={"Content-Type": "application/json"},
                )
                with urllib.request.urlopen(req, timeout=10) as resp:
                    result = json.loads(resp.read())
                assert result["choices"][0]["finish_reason"] in ("stop", "length")
                assert result["usage"]["completion_tokens"] == 4
                return
            except Exception as e:  # noqa: PERF203 — polling until ready
                last_err = e
                time.sleep(1.0)
        pytest.fail(f"graph never became ready: {last_err}")
    finally:
        proc.terminate()
        try:
            proc.wait(15)
        except subprocess.TimeoutExpired:
            proc.kill()


def test_build_mesh_axes():

    from dynamo_tpu.parallel.mesh import MeshConfig, build_mesh

    mesh = build_mesh(MeshConfig(tp=2, dp=2, sp=1, ep=2))
    assert mesh.axis_names == ("dp", "pp", "sp", "ep", "tp")
    assert mesh.devices.shape == (2, 1, 1, 2, 2)
    with pytest.raises(ValueError):
        build_mesh(MeshConfig(tp=16))


def test_resource_allocator_whole_chips():
    from dynamo_tpu.sdk.allocator import ResourceAllocator
    from dynamo_tpu.sdk.decorators import ServiceMeta

    alloc = ResourceAllocator(total_chips=4)
    meta = ServiceMeta(workers=2, resources={"tpu": 1})
    n, envs = alloc.get_worker_env(meta, {})
    assert n == 2
    assert envs[0]["TPU_VISIBLE_DEVICES"] == "0"
    assert envs[1]["TPU_VISIBLE_DEVICES"] == "1"
    # a second service gets the remaining chips, disjoint from the first
    n, envs = alloc.get_worker_env(ServiceMeta(workers=1, resources={"tpu": 2}), {})
    assert envs[0]["TPU_VISIBLE_DEVICES"] == "2,3"


def test_resource_allocator_refuses_a_shared_chip():
    """A chip belongs to one process: a fractional request, which used to put
    two workers on chip 0, is refused (the second could not open the chip)."""
    from dynamo_tpu.sdk.allocator import ResourceAllocator
    from dynamo_tpu.sdk.decorators import ServiceMeta

    alloc = ResourceAllocator(total_chips=2)
    with pytest.raises(ValueError, match="cannot be shared"):
        alloc.get_worker_env(ServiceMeta(workers=2, resources={"tpu": 0.5}), {})
    with pytest.raises(ValueError, match="whole chips"):
        alloc.assign_chips(1.5)
    assert alloc.remaining_chips == 2  # nothing was handed out


def test_resource_allocator_cpu_service_pinned_off_tpu():
    from dynamo_tpu.sdk.allocator import ResourceAllocator
    from dynamo_tpu.sdk.decorators import ServiceMeta

    alloc = ResourceAllocator(total_chips=4)
    _, envs = alloc.get_worker_env(ServiceMeta(workers=1), {})
    assert envs[0] == {"JAX_PLATFORMS": "cpu"}
    # YAML config overrides meta resources/workers
    n, envs = alloc.get_worker_env(
        ServiceMeta(workers=1), {"workers": 3, "resources": {"tpu": 1}}
    )
    assert n == 3
    assert len({e["TPU_VISIBLE_DEVICES"] for e in envs}) == 3


@pytest.mark.parametrize("detected, workers", [(0, 1), (1, 2)])
def test_resource_allocator_fails_without_enough_chips(detected, workers, monkeypatch):
    """A service that asks for chips where none (or too few) are detected
    fails at start-up with a message that says what to do; it used to leave
    every worker to contend for whatever was visible."""
    from dynamo_tpu.sdk.allocator import ResourceAllocator
    from dynamo_tpu.sdk.decorators import ServiceMeta

    alloc = ResourceAllocator(total_chips=detected)
    meta = ServiceMeta(workers=workers, resources={"tpu": 1})
    with pytest.raises(RuntimeError, match=r"tpu: 0.*DYNTPU_DISABLE_TPU_ALLOCATION"):
        alloc.get_worker_env(meta, {})
    # the two ways out the message names
    _, envs = alloc.get_worker_env(meta, {"resources": {"tpu": 0}})
    assert envs == [{"JAX_PLATFORMS": "cpu"}] * workers
    monkeypatch.setenv("DYNTPU_DISABLE_TPU_ALLOCATION", "1")
    _, envs = alloc.get_worker_env(meta, {})
    assert envs == [{}] * workers


def test_one_chip_worker_env_sets_process_bounds():
    from dynamo_tpu.sdk.allocator import chip_env

    assert chip_env([2]) == {
        "TPU_VISIBLE_DEVICES": "2",
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "1,1,1",
    }
    assert chip_env([2, 3]) == {"TPU_VISIBLE_DEVICES": "2,3"}
