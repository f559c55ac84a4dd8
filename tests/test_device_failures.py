"""No quiet route off the device on the --hasher tpu path.

A device-plane failure — a kernel that raises, a backend probe that
fails, a readback that times out — ends the build with exit 1 and its
reason, through the CLI and through a worker alike. Only an operator
who sets MAKISU_TPU_CHUNK_STRICT=0 gets the old degrade (the layer
commits without chunk fingerprints, exit 0). A build that "passed"
while the device did nothing would otherwise be the fastest build
there is.
"""

import json
import time

import pytest

from makisu_tpu import cli
from makisu_tpu.ops import backend, gear
from makisu_tpu.worker import WorkerClient, WorkerServer


def _raising_kernel(monkeypatch):
    def boom(*a, **k):
        raise RuntimeError("synthetic kernel refusal")
    monkeypatch.setattr(gear, "gear_bitmap", boom)
    return "synthetic kernel refusal"


def _failing_probe(monkeypatch):
    monkeypatch.setattr(
        backend, "backend_ready",
        lambda *a, **k: "backend init failed: synthetic, no device")
    return "backend init failed: synthetic, no device"


def _hanging_sync(monkeypatch):
    class HangingWords:
        def __array__(self, dtype=None, copy=None):
            time.sleep(30)

    monkeypatch.setenv("MAKISU_TPU_SYNC_TIMEOUT", "0.2")
    monkeypatch.setattr(gear, "gear_bitmap", lambda *a, **k: HangingWords())
    return "gear bitmap readback did not complete within"


FAULTS = {"kernel": _raising_kernel, "probe": _failing_probe,
          "sync": _hanging_sync}


@pytest.fixture
def context(tmp_path):
    ctx = tmp_path / "ctx"
    ctx.mkdir()
    (ctx / "Dockerfile").write_text("FROM scratch\nCOPY data.bin /d.bin\n")
    (ctx / "data.bin").write_bytes(bytes(range(256)) * 400)
    (tmp_path / "root").mkdir()
    return ctx


def _argv(tmp_path, ctx):
    return ["build", str(ctx), "-t", "fail/t:1", "--hasher", "tpu",
            "--storage", str(tmp_path / "storage"),
            "--root", str(tmp_path / "root")]


def _chunk_lists(tmp_path):
    kv = json.loads((tmp_path / "storage" /
                     "cache_key_value.json").read_text())
    return [json.loads(v).get("chunks") for v, _ in kv.values()
            if v.startswith("{")]


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("strict", [None, "0"])
def test_cli_build(tmp_path, monkeypatch, context, fault, strict):
    # The device formulation, not the C++ route a CPU host would take.
    monkeypatch.setenv("MAKISU_TPU_CHUNK_NATIVE", "0")
    if strict is None:
        monkeypatch.delenv("MAKISU_TPU_CHUNK_STRICT", raising=False)
    else:
        monkeypatch.setenv("MAKISU_TPU_CHUNK_STRICT", strict)
    reason = FAULTS[fault](monkeypatch)
    log_path = tmp_path / "build.log"
    rc = cli.main(["--log-output", str(log_path)] + _argv(tmp_path, context))
    logged = log_path.read_text()
    assert reason in logged
    if strict == "0":
        assert rc == 0
        assert "chunk fingerprinting disabled" in logged
        assert _chunk_lists(tmp_path) == [None]
    else:
        assert rc == 1
        assert "failed to execute command" in logged
        assert "chunk fingerprinting disabled" not in logged


@pytest.fixture
def worker(tmp_path):
    server = WorkerServer(str(tmp_path / "worker.sock"))
    thread = server.serve_background()
    yield server
    server.shutdown()
    server.server_close()
    thread.join(timeout=5)


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("strict", [None, "0"])
def test_worker_build(tmp_path, monkeypatch, context, worker, fault, strict):
    if strict is None:
        monkeypatch.delenv("MAKISU_TPU_CHUNK_STRICT", raising=False)
    else:
        monkeypatch.setenv("MAKISU_TPU_CHUNK_STRICT", strict)
    reason = FAULTS[fault](monkeypatch)
    lines: list[str] = []
    client = WorkerClient(worker.socket_path)
    code = client.build(_argv(tmp_path, context),
                        on_line=lambda p: lines.append(str(p.get("msg"))))
    assert any(reason in line for line in lines)
    if strict == "0":
        assert code == 0
        assert _chunk_lists(tmp_path) == [None]
    else:
        assert code == 1
        assert client.healthz().builds_failed == 1
