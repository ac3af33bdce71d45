"""Live observability exporter (ISSUE 17): /metrics, /healthz, /slo,
/incidents, /trace/tail over FLAGS_tpu_metrics_port.

The acceptance bar: the disabled path is one dict lookup (maybe_serve
returns None without touching sockets); with the flag set an LLMEngine
run is scrapeable mid-flight and the final /slo scrape agrees with the
engine's own ``slo_report()``; and a taken port falls back to an
ephemeral bind instead of crashing the replica.
"""
import json
import socket
import threading
import time
import urllib.request

import jax
import numpy as np
import pytest

from paddle_tpu import serving
from paddle_tpu.core import flags as _flags
from paddle_tpu.models import llama
from paddle_tpu.ops import pallas_ops
from paddle_tpu.profiler import exporter, metrics
from paddle_tpu.serving.autoscale import AutoscalePolicy, ServiceModel


@pytest.fixture(autouse=True)
def _interpret_mode():
    old = pallas_ops._INTERPRET
    pallas_ops._INTERPRET = True
    yield
    pallas_ops._INTERPRET = old


@pytest.fixture(autouse=True)
def _exporter_off():
    """Every test starts and ends with the exporter down, flag off."""
    old = _flags._REGISTRY["FLAGS_tpu_metrics_port"]
    exporter.shutdown()
    yield
    _flags.set_flags({"FLAGS_tpu_metrics_port": old})
    exporter.shutdown()


def _get(port, path):
    try:
        with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}",
                                    timeout=10) as r:
            return r.status, r.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


def _get_json(port, path):
    status, body = _get(port, path)
    assert status == 200, body
    return json.loads(body)


def _tiny_cfg():
    return llama.LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, dtype=jax.numpy.float32,
        use_remat=False)


# ---------------------------------------------------------------------------
# gating
# ---------------------------------------------------------------------------


def test_disabled_path_is_inert():
    _flags.set_flags({"FLAGS_tpu_metrics_port": 0})
    assert exporter.maybe_serve("engine", object()) is None
    assert exporter.active() is None


def test_engine_constructor_does_not_start_exporter_when_off():
    _flags.set_flags({"FLAGS_tpu_metrics_port": 0})
    cfg = _tiny_cfg()
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    serving.LLMEngine(cfg, params, max_running=2, chunk=4, page_size=8,
                      max_model_len=32)
    assert exporter.active() is None


def test_flag_minus_one_binds_ephemeral_port():
    _flags.set_flags({"FLAGS_tpu_metrics_port": -1})
    exp = exporter.maybe_serve()
    assert exp is not None and exp.port > 0
    status, body = _get(exp.port, "/healthz")
    assert status == 200 and json.loads(body)["ok"]


def test_port_conflict_falls_back_to_ephemeral():
    blocker = socket.socket()
    blocker.bind(("127.0.0.1", 0))
    blocker.listen(1)
    taken = blocker.getsockname()[1]
    try:
        _flags.set_flags({"FLAGS_tpu_metrics_port": taken})
        exp = exporter.maybe_serve()
        assert exp is not None
        assert exp.port != taken and exp.port > 0
        assert _get(exp.port, "/healthz")[0] == 200
    finally:
        blocker.close()


def test_portfile_records_bound_port(tmp_path, monkeypatch):
    portfile = tmp_path / "port"
    monkeypatch.setenv("PADDLE_TPU_METRICS_PORTFILE", str(portfile))
    _flags.set_flags({"FLAGS_tpu_metrics_port": -1})
    exp = exporter.maybe_serve()
    assert int(portfile.read_text()) == exp.port


# ---------------------------------------------------------------------------
# endpoints
# ---------------------------------------------------------------------------


def test_metrics_endpoint_serves_prometheus_text():
    _flags.set_flags({"FLAGS_tpu_metrics_port": -1,
                      "FLAGS_tpu_metrics": True})
    try:
        metrics.counter("exporter_test_total", "counter under test").inc(3)
        exp = exporter.maybe_serve()
        status, body = _get(exp.port, "/metrics")
        assert status == 200
        assert "exporter_test_total 3" in body
    finally:
        _flags.set_flags({"FLAGS_tpu_metrics": False})
        metrics.reset()


def test_incidents_and_trace_tail_endpoints():
    from paddle_tpu.runtime import watchdog
    _flags.set_flags({"FLAGS_tpu_metrics_port": -1})
    exp = exporter.maybe_serve()
    watchdog.record_incident("exporter_test", detail="synthetic")
    doc = _get_json(exp.port, "/incidents?n=5")
    assert doc["count"] >= 1
    assert doc["tail"][-1]["kind"] == "exporter_test"
    doc = _get_json(exp.port, "/trace/tail?n=5")
    assert doc["enabled"] is False and doc["tail"] == []
    assert _get(exp.port, "/nope")[0] == 404


# ---------------------------------------------------------------------------
# live engine scrape
# ---------------------------------------------------------------------------


def test_concurrent_scrape_during_engine_run_matches_final_report():
    """Scrapes from a background thread while the engine steps must
    never error, and the post-run /slo scrape equals the engine's own
    slo_report()."""
    _flags.set_flags({"FLAGS_tpu_metrics_port": -1})
    cfg = _tiny_cfg()
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    eng = serving.LLMEngine(cfg, params, max_running=4, chunk=4,
                            page_size=8, max_model_len=32,
                            slo=serving.SLOConfig(ttft_p95_s=10.0,
                                                  latency_p95_s=10.0))
    exp = exporter.active()
    assert exp is not None, "engine constructor must start the exporter"

    rng = np.random.RandomState(0)
    for i in range(6):
        eng.add_request(list(rng.randint(0, 128, 5 + i)), 4)

    scraped, errors = [], []
    stop = threading.Event()

    def scraper():
        while not stop.is_set():
            try:
                scraped.append(_get_json(exp.port, "/slo"))
                _get(exp.port, "/metrics")
                _get(exp.port, "/healthz")
            except Exception as e:  # pragma: no cover - failure detail
                errors.append(e)
            time.sleep(0.002)

    t = threading.Thread(target=scraper, daemon=True)
    t.start()
    steps = 0
    while eng.has_work():
        eng.step()
        steps += 1
        assert steps < 500
    stop.set()
    t.join(timeout=10)
    assert not errors, errors
    assert scraped, "scraper never completed a request"

    final = _get_json(exp.port, "/slo")
    (eng_view,) = final["engines"]
    own = eng.slo_report()
    assert eng_view["ttft_p95_s"] == pytest.approx(
        float(own["ttft_p95_s"]), rel=1e-6)
    assert eng_view["latency_p95_s"] == pytest.approx(
        float(own["latency_p95_s"]), rel=1e-6)
    health = _get_json(exp.port, "/healthz")
    assert health["engines"][0]["num_running"] == 0


def test_router_attachment_exposes_burn_rates_and_recommendation():
    _flags.set_flags({"FLAGS_tpu_metrics_port": -1})
    cfg = _tiny_cfg()
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    eng = serving.LLMEngine(cfg, params, max_running=2, chunk=4,
                            page_size=8, max_model_len=32)
    clock_t = [0.0]
    model = ServiceModel(max_running=2, chunk=4, page_size=8, num_pages=9,
                         max_model_len=32, max_queue=32)
    policy = AutoscalePolicy(model, slo_ttft_s=0.5,
                             clock=lambda: clock_t[0])
    router = serving.Router([("r0", eng)], autoscaler=policy,
                            clock=lambda: clock_t[0])
    exp = exporter.active()
    doc = _get_json(exp.port, "/slo")
    assert doc["router"]["live_replicas"] == ["r0"]
    assert doc["burn_rates"] is not None
    health = _get_json(exp.port, "/healthz")
    assert health["router"]["replicas"] == {"r0": "live"}
