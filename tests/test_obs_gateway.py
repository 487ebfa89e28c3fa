"""Operational HTTP gateway: endpoint matrix, LB probe semantics, admin
auth, bounded-pool lifecycle — all over a *live* SearchService."""

import json
import socket
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from raft_tpu import obs, serve
from raft_tpu.neighbors import brute_force
from raft_tpu.obs import export as obs_export
from raft_tpu.obs import gateway as obs_gateway

N, D = 192, 12


def _request(url, path, *, method="GET", headers=None, timeout=30.0):
    """(status, content-type, body bytes) — errors answered, not raised."""
    req = urllib.request.Request(
        url + path, headers=headers or {}, method=method
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, resp.headers.get("Content-Type"), resp.read()
    except urllib.error.HTTPError as err:
        return err.code, err.headers.get("Content-Type"), err.read()


def _jget(url, path, **kw):
    status, _, body = _request(url, path, **kw)
    return status, json.loads(body)


@pytest.fixture
def dataset():
    rng = np.random.default_rng(7)
    return rng.random((N, D), dtype=np.float32)


@pytest.fixture
def service(dataset):
    """Live multi-index service owning an ephemeral-port gateway."""
    svc = serve.SearchService(
        k=3, max_batch=8, max_delay_ms=0.5,
        gateway=obs_gateway.GatewayConfig(port=0),
    )
    for name in ("wiki", "code"):
        svc.add_index(
            name, serve.MutableIndex(brute_force.build(dataset)),
            warmup=True,
        )
    yield svc
    svc.stop()


def _url(svc):
    return svc.gateway.url


# -- read plane --------------------------------------------------------------

def test_endpoint_matrix(service, dataset):
    url = _url(service)

    status, ctype, body = _request(url, "/metrics")
    assert status == 200
    assert ctype == obs_export.PROMETHEUS_CONTENT_TYPE
    assert b"raft_tpu_gateway_requests_total" in body
    assert not body.rstrip().endswith(b"# EOF")

    status, health = _jget(url, "/healthz")
    assert status == 200
    assert health["status"] in ("OK", "DEGRADED")
    assert set(health["indexes"]) == {"wiki", "code"}

    status, ready = _jget(url, "/readyz")
    assert status == 200 and ready["ready"] is True

    status, snap = _jget(url, "/snapshot")
    assert status == 200
    assert set(snap["indexes"]) == {"wiki", "code"}
    assert "registry" in snap and "health" in snap

    status, incidents = _jget(url, "/incidents")
    assert status == 200 and "open" in json.dumps(incidents)

    status, flight = _jget(url, "/flight")
    assert status == 200 and "recorded_total" in flight

    # subsystems this service doesn't run answer 404, not 500
    assert _jget(url, "/slo")[0] == 404
    assert _jget(url, "/autotune")[0] == 404

    q = ",".join(str(x) for x in dataset[0])
    status, plan = _jget(url, f"/explain?name=wiki&q={q}")
    assert status == 200
    assert plan["schema"] == "raft_tpu.explain"
    assert plan["outcome"]["outcome"] == "ok"


def test_metrics_accept_negotiation(service):
    url = _url(service)
    status, ctype, body = _request(
        url, "/metrics",
        headers={"Accept": "application/openmetrics-text; version=1.0.0"},
    )
    assert status == 200
    assert ctype == obs_export.OPENMETRICS_CONTENT_TYPE
    assert body.rstrip().endswith(b"# EOF")

    # the scraper's classic preference keeps classic text
    status, ctype, _ = _request(
        url, "/metrics",
        headers={"Accept": "text/plain;q=0.9,"
                           "application/openmetrics-text;q=0.1"},
    )
    assert ctype == obs_export.PROMETHEUS_CONTENT_TYPE


def test_slo_and_autotune_routes_with_subsystems(dataset):
    svc = serve.SearchService(
        k=3, max_batch=8, slo=True, autotune=obs.Autotuner(), start=False,
        gateway=obs_gateway.GatewayConfig(port=0),
    )
    svc.gateway.start()
    try:
        svc.add_index(
            "wiki", serve.MutableIndex(brute_force.build(dataset)),
            warmup=True,
        )
        url = _url(svc)
        status, slo = _jget(url, "/slo")
        assert status == 200 and "wiki-availability" in slo["specs"]
        status, tune = _jget(url, "/autotune")
        assert status == 200
        assert "wiki" in tune["effort"]
        assert tune["effort"]["wiki"]["effective_level"] >= 0
    finally:
        svc.stop()


def test_error_paths_and_request_counter(service):
    url = _url(service)
    assert _request(url, "/no/such/route")[0] == 404
    assert _request(url, "/metrics", method="POST")[0] == 405
    assert _request(url, "/incidents/nope")[0] == 404
    assert _jget(url, "/explain?name=wiki")[0] == 400
    assert _jget(url, "/explain?name=ghost&q=1,2")[0] == 404
    assert _jget(url, "/explain?name=wiki&q=a,b")[0] == 400

    # the gateway's own traffic is in its own scrape, by matched route —
    # the raw (unbounded) path never becomes a label value
    _, _, body = _request(url, "/metrics")
    text = body.decode()
    assert 'route="unknown"' in text and 'code="404"' in text
    assert 'route="/metrics"' in text and 'code="405"' in text
    assert "/no/such/route" not in text


def test_readyz_flips_across_warmup(dataset):
    svc = serve.SearchService(
        k=3, max_batch=8, gateway=obs_gateway.GatewayConfig(port=0)
    )
    try:
        svc.add_index(
            "cold", serve.MutableIndex(brute_force.build(dataset)),
            warmup=False,
        )
        url = _url(svc)
        status, ready = _jget(url, "/readyz")
        assert status == 503 and ready["ready"] is False
        # liveness still answers 200 while the gate is closed
        assert _jget(url, "/healthz")[0] == 200
        svc.warmup()
        status, ready = _jget(url, "/readyz")
        assert status == 200 and ready["indexes"]["cold"] is True
    finally:
        svc.stop()


def test_concurrent_scrapes_zero_recompiles(service, dataset):
    url = _url(service)
    service.warmup()
    stop = threading.Event()
    scrape_errors = []

    def scraper():
        while not stop.is_set():
            for path in ("/metrics", "/healthz", "/readyz"):
                status = _request(url, path)[0]
                if status != 200:
                    scrape_errors.append((path, status))

    scrapers = [threading.Thread(target=scraper) for _ in range(2)]
    for t in scrapers:
        t.start()
    try:
        futures = [
            service.submit(name, dataset[i % N])
            for i in range(120)
            for name in ("wiki", "code")
        ]
        for fut in futures:
            dists, ids = fut.result(timeout=60)
            assert ids.shape[-1] == 3
    finally:
        stop.set()
        for t in scrapers:
            t.join(timeout=10)
    assert not scrape_errors
    for name in ("wiki", "code"):
        assert service.stats(name)["recompiles"] == 0


# -- admin plane -------------------------------------------------------------

def test_admin_plane_default_off_is_invisible(service):
    url = _url(service)
    for route in ("/admin/compact?name=wiki", "/admin/effort_pin",
                  "/admin/flight_dump", "/admin/archive_dump"):
        assert _request(url, route, method="POST")[0] == 404


def test_admin_enabled_without_token_fails_closed(dataset):
    svc = serve.SearchService(
        k=3, max_batch=8,
        gateway=obs_gateway.GatewayConfig(port=0, admin=True, token=None),
    )
    try:
        url = _url(svc)
        assert _request(url, "/admin/flight_dump", method="POST")[0] == 403
    finally:
        svc.stop()


def test_admin_token_enforcement(dataset):
    svc = serve.SearchService(
        k=3, max_batch=8, autotune=obs.Autotuner(), start=False,
        gateway=obs_gateway.GatewayConfig(
            port=0, admin=True, token="s3cret"
        ),
    )
    svc.gateway.start()
    try:
        svc.add_index(
            "wiki", serve.MutableIndex(brute_force.build(dataset)),
            warmup=True,
        )
        url = _url(svc)
        status, _, _ = _request(url, "/admin/flight_dump", method="POST")
        assert status == 401
        status, _, _ = _request(
            url, "/admin/flight_dump", method="POST",
            headers={"Authorization": "Bearer wrong"},
        )
        assert status == 401
        auth = {"Authorization": "Bearer s3cret"}

        status, dump = _jget(
            url, "/admin/flight_dump", method="POST", headers=auth
        )
        assert status == 200 and dump["path"]

        status, dump = _jget(
            url, "/admin/archive_dump", method="POST", headers=auth
        )
        assert status == 200 and dump["path"]

        # effort pin: set, observe through the arbiter, clear
        status, pin = _jget(
            url, "/admin/effort_pin?name=wiki&level=1",
            method="POST", headers=auth,
        )
        assert status == 200 and pin["pinned"] == 1
        assert svc.effort_arbiter("wiki").effective_level() == 1
        status, pin = _jget(
            url, "/admin/effort_pin?name=wiki&level=-1",
            method="POST", headers=auth,
        )
        assert status == 200 and pin["pinned"] is None
        assert svc.effort_arbiter("wiki").effective_level() == 0

        # compact without a compactor is a conflict, not a crash
        status, _ = _jget(
            url, "/admin/compact?name=wiki", method="POST", headers=auth
        )
        assert status == 409
        # GET on an admin route is a method error once authorized routes
        # exist at that path
        assert _request(url, "/admin/flight_dump")[0] == 405
    finally:
        svc.stop()


# -- lifecycle ---------------------------------------------------------------

def _gateway_threads():
    return [
        t for t in threading.enumerate()
        if t.is_alive() and t.name.startswith("raft-tpu-gateway")
    ]


def test_stop_closes_gateway_and_leaves_no_threads(dataset):
    svc = serve.SearchService(
        k=3, max_batch=8, gateway=obs_gateway.GatewayConfig(port=0)
    )
    svc.add_index(
        "wiki", serve.MutableIndex(brute_force.build(dataset)), warmup=True
    )
    url = _url(svc)
    port = svc.gateway.port
    assert _request(url, "/healthz")[0] == 200
    assert _gateway_threads()
    svc.stop()
    deadline = time.monotonic() + 5.0
    while _gateway_threads() and time.monotonic() < deadline:
        time.sleep(0.01)
    assert not _gateway_threads(), _gateway_threads()
    with pytest.raises(OSError):
        socket.create_connection(("127.0.0.1", port), timeout=0.5).close()
    svc.stop()  # idempotent


def test_standalone_gateway_and_bind_failure():
    # hold a port hostage so main() sees EADDRINUSE
    blocker = socket.socket()
    blocker.bind(("127.0.0.1", 0))
    blocker.listen(1)
    taken = blocker.getsockname()[1]
    try:
        assert obs_gateway.main(["--port", str(taken)]) == 1
    finally:
        blocker.close()


def test_standalone_main_serves_and_drains():
    probed = {}

    def ready(gateway, stop_event):
        url = gateway.url
        probed["readyz"] = _request(url, "/readyz")[0]
        probed["metrics"] = _request(url, "/metrics")[0]
        probed["snapshot"] = _request(url, "/snapshot")[0]
        probed["explain"] = _request(url, "/explain?name=x&q=1")[0]
        stop_event.set()

    rc = obs_gateway.main(["--port", "0"], ready=ready)
    assert rc == 0
    assert probed["metrics"] == 200
    assert probed["snapshot"] == 200
    assert probed["readyz"] == 503  # no service attached: never ready
    assert probed["explain"] == 404
    assert not _gateway_threads()


def test_negotiate_content_type_table():
    cases = {
        None: obs_export.PROMETHEUS_CONTENT_TYPE,
        "": obs_export.PROMETHEUS_CONTENT_TYPE,
        "text/plain": obs_export.PROMETHEUS_CONTENT_TYPE,
        "*/*": obs_export.PROMETHEUS_CONTENT_TYPE,
        "application/openmetrics-text": obs_export.OPENMETRICS_CONTENT_TYPE,
        "application/openmetrics-text;version=1.0.0;q=0.75,"
        "text/plain;version=0.0.4;q=0.5,*/*;q=0.1":
            obs_export.OPENMETRICS_CONTENT_TYPE,
        "application/openmetrics-text;q=0":
            obs_export.PROMETHEUS_CONTENT_TYPE,
        "text/plain;q=bogus,application/openmetrics-text;q=0.5":
            obs_export.PROMETHEUS_CONTENT_TYPE,
    }
    for accept, expected in cases.items():
        assert obs_export.negotiate_content_type(accept) == expected, accept
