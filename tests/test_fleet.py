"""Fleet subsystem tests (ISSUE 18): ring, router, manager drills.

Three layers, cheapest first:

1. **Ring units** — pure hash math, no HTTP: balance, the degrade/restore
   prefix property, minimal remapping on ejection, spill (preference)
   order.
2. **Router units** — a real FleetRouter over stub HTTP engines (no jax):
   hash affinity, spill on 429/503, health-ladder ejection + re-admission
   via the injectable fetch, queue-pressure degrade, tier-saturated shed,
   request-id forwarding, /metrics and /metrics/fleet surfaces.
3. **Chaos drills** (marker ``chaos``, real ``cli.serve`` subprocesses on
   the CPU mesh) — the rolling-restart acceptance drill (zero failed
   requests tier-wide, bit-identical answers, zero AOT compiles on the
   replacement's warmup) and the engine-kill drill (fault site
   ``fleet.engine:kill`` scoped to one engine with ``%hostN``; the router
   ejects it and in-flight work spills to the ring successor).
"""

import json
import os
import sys
import threading
import time
import urllib.error
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from pathlib import Path

import pytest

from helpers import write_vocab

from ml_recipe_tpu.fleet import (
    EngineEndpoint,
    FleetManager,
    FleetRouter,
    HashRing,
)

REPO_ROOT = str(Path(__file__).resolve().parents[1])


# ---------------------------------------------------------------------------
# 1. ring units
# ---------------------------------------------------------------------------


def _placement(ring, keys):
    return {k: ring.node_for(k) for k in keys}


def test_ring_balance_within_bounds():
    ring = HashRing(replicas=64)
    for n in ("a", "b", "c"):
        ring.add(n)
    keys = [f"doc-{i}" for i in range(3000)]
    counts = {"a": 0, "b": 0, "c": 0}
    for k in keys:
        counts[ring.node_for(k)] += 1
    for n, c in counts.items():
        share = c / len(keys)
        # 64 vnodes/node keeps shares near 1/3; catastrophic skew (one
        # node owning almost nothing / almost everything) is the bug class
        assert 0.15 < share < 0.55, (n, counts)


def test_ring_degrade_restore_roundtrip_is_noop():
    ring = HashRing(replicas=64)
    for n in ("a", "b", "c"):
        ring.add(n)
    keys = [f"doc-{i}" for i in range(500)]
    before = _placement(ring, keys)
    ring.set_weight("b", 0.25)
    degraded = _placement(ring, keys)
    # a degraded node keeps a PREFIX of its vnodes: every key that moved
    # moved OFF b, none moved between a and c
    moved = {k for k in keys if degraded[k] != before[k]}
    assert moved, "weight cut to 0.25 should shed keys"
    assert all(before[k] == "b" for k in moved)
    ring.set_weight("b", 1.0)
    assert _placement(ring, keys) == before


def test_ring_removal_remaps_only_removed_nodes_keys():
    ring = HashRing(replicas=64)
    for n in ("a", "b", "c"):
        ring.add(n)
    keys = [f"doc-{i}" for i in range(500)]
    before = _placement(ring, keys)
    ring.remove("b")
    after = _placement(ring, keys)
    for k in keys:
        if before[k] != "b":
            assert after[k] == before[k], k  # everyone else's cache stays warm
        else:
            assert after[k] in ("a", "c")
    ring.remove("b")  # eject is idempotent
    assert len(ring) == 2 and "b" not in ring


def test_ring_preference_is_distinct_spill_order():
    ring = HashRing(replicas=8)
    for n in ("a", "b", "c"):
        ring.add(n)
    pref = ring.preference("doc-1")
    assert sorted(pref) == ["a", "b", "c"]  # distinct, covers the ring
    assert pref[0] == ring.node_for("doc-1")
    assert ring.preference("doc-1", limit=2) == pref[:2]
    # the spill target is the successor: removing the owner promotes it
    ring.remove(pref[0])
    assert ring.node_for("doc-1") == pref[1]


def test_ring_empty_and_validation():
    ring = HashRing(replicas=4)
    assert ring.node_for("x") is None
    assert ring.preference("x") == []
    with pytest.raises(ValueError):
        ring.add("a", weight=0.0)
    with pytest.raises(KeyError):
        ring.set_weight("ghost", 0.5)
    with pytest.raises(ValueError):
        HashRing(replicas=0)


# ---------------------------------------------------------------------------
# 2. router units over stub engines
# ---------------------------------------------------------------------------


class _StubHandler(BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"

    def log_message(self, fmt, *args):
        pass

    def _json(self, code, payload):
        body = json.dumps(payload).encode("utf-8")
        self.send_response(code)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def do_GET(self):  # noqa: N802 - http.server API
        stub = self.server.stub
        if self.path == "/healthz":
            self._json(200, dict(stub.health))
        elif self.path == "/metrics":
            body = stub.metrics_text.encode("utf-8")
            self.send_response(200)
            self.send_header("Content-Type", "text/plain; version=0.0.4")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)
        else:
            self._json(404, {"error": "no route"})

    def do_POST(self):  # noqa: N802 - http.server API
        stub = self.server.stub
        length = int(self.headers.get("Content-Length", 0))
        self.rfile.read(length)
        with stub.lock:
            stub.requests.append(self.headers.get("X-Request-Id"))
        if stub.qa_status != 200:
            self._json(stub.qa_status, {"error": "stub refusing"})
            return
        self._json(200, {
            "answer": f"answer from {stub.name}",
            "label": "short",
            "latency_ms": 1.0,
        })


class StubEngine:
    """A stdlib HTTP engine double: scriptable /v1/qa status + /healthz."""

    def __init__(self, name):
        self.name = name
        self.qa_status = 200
        self.health = {"status": "ok", "queue_depth": 0, "queue_limit": 100}
        self.metrics_text = "# TYPE qa_requests_total counter\nqa_requests_total 7\n"
        self.requests = []
        self.lock = threading.Lock()
        self._httpd = ThreadingHTTPServer(("127.0.0.1", 0), _StubHandler)
        self._httpd.daemon_threads = True
        self._httpd.stub = self
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, daemon=True)
        self._thread.start()

    @property
    def port(self):
        return self._httpd.server_address[1]

    def endpoint(self):
        return EngineEndpoint(self.name, "127.0.0.1", self.port)

    def close(self):
        self._httpd.shutdown()
        self._httpd.server_close()
        self._thread.join(timeout=5)


@pytest.fixture()
def stub_tier():
    stubs = [StubEngine(f"engine{i}") for i in range(2)]
    routers = []

    def build(**kwargs):
        kwargs.setdefault("health_poll_s", 30.0)  # tests drive _poll_once
        router = FleetRouter([s.endpoint() for s in stubs], **kwargs)
        routers.append(router)
        return router.start()

    yield stubs, build
    for router in routers:
        router.close()
    for s in stubs:
        s.close()


def _post_qa(router, document, question="q ?"):
    req = urllib.request.Request(
        f"http://{router.host}:{router.port}/v1/qa",
        data=json.dumps(
            {"question": question, "document": document}).encode("utf-8"),
        headers={"Content-Type": "application/json"},
    )
    try:
        with urllib.request.urlopen(req, timeout=30) as resp:
            return resp.status, json.loads(resp.read()), dict(resp.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}"), dict(e.headers)


def test_router_hash_affinity_pins_documents(stub_tier):
    stubs, build = stub_tier
    router = build()
    # every repeat of one document lands on the SAME engine
    engines_hit = set()
    for _ in range(6):
        status, _, headers = _post_qa(router, "the same document")
        assert status == 200
        engines_hit.add(headers["X-Fleet-Engine"])
    assert len(engines_hit) == 1
    owner = engines_hit.pop()
    counts = {s.name: len(s.requests) for s in stubs}
    assert counts[owner] == 6
    assert sum(counts.values()) == 6
    # distinct documents spread: with 64 vnodes, 40 docs never all collide
    for i in range(40):
        _post_qa(router, f"doc number {i}")
    assert all(len(s.requests) > 0 for s in stubs)
    assert int(router.m_requests.value) == 46


def test_router_spills_to_successor_on_refusal(stub_tier):
    stubs, build = stub_tier
    router = build()
    doc = "a pinned document"
    _, _, headers = _post_qa(router, doc)
    owner = next(s for s in stubs if s.name == headers["X-Fleet-Engine"])
    other = next(s for s in stubs if s is not owner)
    owner.qa_status = 503
    status, body, headers = _post_qa(router, doc)
    assert status == 200
    assert headers["X-Fleet-Engine"] == other.name
    assert body["answer"] == f"answer from {other.name}"
    assert int(router.m_spilled.value) == 1
    assert int(router.m_shed.value) == 0


def test_router_sheds_with_retry_after_when_tier_saturated(stub_tier):
    stubs, build = stub_tier
    router = build()
    for s in stubs:
        s.qa_status = 429
    status, body, headers = _post_qa(router, "any document")
    assert status == 503
    assert headers["Retry-After"] == "1"
    assert "request_id" in body
    assert int(router.m_shed.value) == 1
    # refusals walked the health ladder on both engines
    assert int(router.m_degraded.value) >= 1


def test_router_health_ladder_ejects_and_readmits(stub_tier):
    stubs, build = stub_tier
    sick, healthy = stubs
    responses = {"mode": "fail"}

    def fetch(url, timeout):
        if f":{sick.port}/" in url and responses["mode"] == "fail":
            raise OSError("connection refused")
        return json.dumps(
            {"status": "ok", "queue_depth": 0, "queue_limit": 100})

    router = build(fetch=fetch, eject_after=2)
    assert int(router.m_in_ring.value) == 2

    router._poll_once()  # failure 1: weight-reduced, still in ring
    assert int(router.m_degraded.value) == 1
    assert int(router.m_ejections.value) == 0
    assert router.health()["engines"][sick.name]["in_ring"]

    router._poll_once()  # failure 2: ejected
    assert int(router.m_ejections.value) == 1
    assert int(router.m_in_ring.value) == 1
    assert not router.health()["engines"][sick.name]["in_ring"]
    assert int(router.m_poll_failures.value) == 2

    # with the sick engine off the ring every document routes to the
    # healthy one — no spill accounting, this is steady-state routing
    for i in range(6):
        status, _, headers = _post_qa(router, f"doc {i}")
        assert status == 200
        assert headers["X-Fleet-Engine"] == healthy.name
    assert int(router.m_spilled.value) == 0

    responses["mode"] = "ok"  # recovery: next poll re-admits at full weight
    router._poll_once()
    assert int(router.m_readmissions.value) == 1
    assert int(router.m_in_ring.value) == 2
    assert router.health()["engines"][sick.name]["weight"] == 1.0


def test_router_queue_pressure_degrades_without_ejection(stub_tier):
    stubs, build = stub_tier
    pressured = stubs[0]
    pressured.health = {"status": "ok", "queue_depth": 90, "queue_limit": 100}
    router = build(queue_pressure=0.75, eject_after=2)
    for _ in range(5):
        router._poll_once()
    state = router.health()["engines"][pressured.name]
    # saturated-but-healthy: keyspace share shrinks, ejection counter
    # never advances no matter how many polls see the pressure
    assert state["in_ring"]
    assert state["weight"] == router.degrade_weight
    assert state["consecutive_failures"] == 0
    assert int(router.m_ejections.value) == 0
    assert int(router.m_degraded.value) == 1
    pressured.health = {"status": "ok", "queue_depth": 0, "queue_limit": 100}
    router._poll_once()
    assert router.health()["engines"][pressured.name]["weight"] == 1.0


def test_router_forwards_request_id_and_reports_metrics(stub_tier):
    stubs, build = stub_tier
    router = build()
    status, _, headers = _post_qa(router, "traced document")
    assert status == 200
    rid = headers["X-Request-Id"]
    owner = next(s for s in stubs if s.name == headers["X-Fleet-Engine"])
    assert owner.requests == [rid]  # the engine saw the router's id

    with urllib.request.urlopen(
        f"http://{router.host}:{router.port}/metrics", timeout=10
    ) as resp:
        page = resp.read().decode("utf-8")
    assert "fleet_requests_total 1" in page
    assert 'fleet_engine_requests_total{engine="%s"} 1' % owner.name in page
    assert "fleet_request_latency_seconds_bucket" in page
    assert "fleet_hop_latency_seconds_bucket" in page

    # /metrics/fleet aggregates the ENGINE pages (qa_* namespace)
    with urllib.request.urlopen(
        f"http://{router.host}:{router.port}/metrics/fleet", timeout=10
    ) as resp:
        fleet_page = resp.read().decode("utf-8")
    assert "qa_requests_total" in fleet_page
    assert "14" in fleet_page  # 7 per stub, summed across 2 engines

    with urllib.request.urlopen(
        f"http://{router.host}:{router.port}/healthz", timeout=10
    ) as resp:
        health = json.loads(resp.read())
    assert health["status"] == "ok"
    assert set(health["engines"]) == {s.name for s in stubs}


def test_router_rejects_malformed_bodies(stub_tier):
    stubs, build = stub_tier
    router = build()
    url = f"http://{router.host}:{router.port}/v1/qa"
    req = urllib.request.Request(
        url, data=b"not json", headers={"Content-Type": "application/json"})
    with pytest.raises(urllib.error.HTTPError) as exc:
        urllib.request.urlopen(req, timeout=10)
    assert exc.value.code == 400
    req = urllib.request.Request(
        url, data=json.dumps({"question": "q"}).encode("utf-8"),
        headers={"Content-Type": "application/json"})
    with pytest.raises(urllib.error.HTTPError) as exc:
        urllib.request.urlopen(req, timeout=10)
    assert exc.value.code == 400
    assert all(not s.requests for s in stubs)  # nothing was forwarded


def test_router_rejects_unknown_routing():
    with pytest.raises(ValueError):
        FleetRouter(routing="round-robin")


# ---------------------------------------------------------------------------
# 3. chaos drills: real cli.serve children behind the router
# ---------------------------------------------------------------------------

_QUESTIONS = [
    ("what is the capital of england ?",
     "<P> London is the capital of England . </P> "
     "<P> Big Ben was built in the city . </P>"),
    ("what runs through london ?",
     "<P> The river Thames runs through London . </P> "
     "<P> The city was built over the river . </P>"),
    ("what was built in the city ?",
     "<P> Big Ben was built in the city . </P> "
     "<P> The tower is in London . </P>"),
    ("what is the quick fox ?",
     "<P> The quick brown fox jumps over the lazy dog . </P> "
     "<P> The dog was lazy . </P>"),
]


def _fleet_env(extra=None):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    if extra:
        env.update(extra)
    return env


def _engine_argv(vocab):
    # bucket 8x64 on bert-tiny: the SAME program test_serve_chaos.py (and
    # the conftest-placed JAX compile cache) already compile — warmup here
    # is a cache read, keeping the drill inside the tier-1 time budget
    return [
        "--model", "bert-tiny",
        "--vocab_file", str(vocab),
        "--lowercase",
        "--buckets", "8x64",
        "--max_batch_delay_ms", "5",
        "--max_question_len", "16",
        "--doc_stride", "24",
        "--hbm_preflight", "false",
    ]


def _post_fleet(router, question, document, timeout=60.0):
    req = urllib.request.Request(
        f"http://{router.host}:{router.port}/v1/qa",
        data=json.dumps(
            {"question": question, "document": document}).encode("utf-8"),
        headers={"Content-Type": "application/json"},
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}")


@pytest.mark.chaos
def test_fleet_rolling_restart_zero_compiles_zero_failures(tmp_path):
    """The ISSUE-18 acceptance drill: a 2-engine tier under live load
    rolls through a restart with zero failed requests tier-wide, zero AOT
    compiles on the replacement's warmup, and bit-identical answers
    before/after."""
    vocab = write_vocab(tmp_path)
    router = FleetRouter(health_poll_s=0.3)
    manager = FleetManager(
        _engine_argv(vocab), n_engines=2, run_dir=tmp_path / "fleet",
        # the zero-compile relaunch rides the shared AOT program store,
        # which exists only where a directory is named for it
        env=_fleet_env({"MLRT_AOT_CACHE": str(tmp_path / "aot")}),
        router=router,
    )
    try:
        manager.start()
        router.start()

        def snapshot():
            answers = []
            for q, d in _QUESTIONS:
                status, body = _post_fleet(router, q, d)
                assert status == 200, body
                answers.append({k: body.get(k) for k in
                                ("answer", "label", "score", "start", "end")})
            return answers

        before = snapshot()

        # live load riding through the whole rolling restart
        stop = threading.Event()
        results = []
        res_lock = threading.Lock()

        def load():
            i = 0
            while not stop.is_set():
                q, d = _QUESTIONS[i % len(_QUESTIONS)]
                status, body = _post_fleet(router, q, d)
                with res_lock:
                    results.append((status, body.get("answer")))
                i += 1

        loader = threading.Thread(target=load)
        loader.start()
        try:
            reports = manager.rolling_restart()
        finally:
            stop.set()
            loader.join(timeout=120)

        assert len(reports) == 2
        for report in reports:
            assert report["drain_exit"] == "clean", report
            # the tentpole economics: the replacement warmed up entirely
            # off the shared AOT program store
            assert report["aot_misses"] == 0, report
            assert report["aot_hits"] > 0, report
            assert report["new_port"] != 0

        assert results, "live load never completed a request"
        failed = [r for r in results if r[0] != 200]
        assert not failed, f"{len(failed)}/{len(results)} failed: {failed[:5]}"

        # identical params (same seed, no checkpoint) + identical programs
        # => the restarted tier answers bit-identically
        assert snapshot() == before

        assert int(router.m_ejections.value) == 0  # cordon != ejection
        assert int(router.m_readmissions.value) == 2
    finally:
        outcome = manager.stop()
        router.close()
    assert set(outcome.values()) <= {"clean"}, outcome


@pytest.mark.chaos
def test_fleet_engine_kill_ejects_and_spills(tmp_path):
    """Kill one engine mid-load (fault site ``fleet.engine:kill`` scoped
    to engine 1 via ``%host1``): every in-flight request either retries
    onto the ring successor or fails with a clean 503 — never a hang —
    and the router ejects the dead engine within the health-poll
    interval."""
    vocab = write_vocab(tmp_path)
    router = FleetRouter(health_poll_s=0.3, eject_after=2)
    manager = FleetManager(
        _engine_argv(vocab), n_engines=2, run_dir=tmp_path / "fleet",
        # engine 1 exits KILL_EXIT_CODE (89) on its 3rd admitted request;
        # engine 0 never sees the fault
        env=_fleet_env({"MLRT_FAULTS": "fleet.engine:kill@3%host1"}),
        router=router,
    )
    try:
        manager.start()
        router.start()

        statuses = []
        for i in range(24):
            q, d = _QUESTIONS[i % len(_QUESTIONS)]
            status, _ = _post_fleet(
                router, q, f"{d} <P> padding token number {i} . </P>")
            statuses.append(status)

        assert set(statuses) <= {200, 503}, statuses
        assert statuses.count(200) >= len(statuses) // 2, statuses

        # the kill was observed as a spill (in-flight retry on the
        # successor) and the health poll ejected the corpse
        deadline = time.monotonic() + 10 * router.health_poll_s
        while int(router.m_ejections.value) == 0 \
                and time.monotonic() < deadline:
            time.sleep(0.05)
        assert int(router.m_ejections.value) >= 1
        assert int(router.m_spilled.value) >= 1
        assert int(router.m_in_ring.value) == 1

        # the supervisor classifies the corpse as a crash and relaunches
        # it; the replacement re-enters the ring
        events = manager.reap()
        assert any(e["node"] == "engine1" and e["class"] == "crash"
                   and e["relaunched"] for e in events), events
        deadline = time.monotonic() + 60
        while int(router.m_in_ring.value) < 2 \
                and time.monotonic() < deadline:
            time.sleep(0.1)
        assert int(router.m_in_ring.value) == 2
        status, body = _post_fleet(router, *_QUESTIONS[0])
        assert status == 200, body
    finally:
        manager.stop()
        router.close()
