"""Replicated serving fleet suite (marker ``fleet``):
tools/run_tier1.sh --fleet-only.

The acceptance pins (ISSUE 9):

- per-replica circuit breakers: error/timeout-rate threshold opens,
  decorrelated-jitter backoff, half-open single-probe recovery — every
  transition a ``breaker_transition`` record;
- committed-version routing: reads route ONLY to replicas at the max
  version held by a read quorum (monotonic), every response echoes
  ``X-Pinned-Version``, and a replica that swapped mid-flight answers
  409 to the router's pin so one client session never observes mixed
  versions;
- single-writer forwarding: writer loss flips the fleet READ-ONLY with
  a loud ``fleet_degraded`` record (no failover, no split-brain);
- zero-downtime rolling reload: drain → /reload → re-probe → rejoin one
  replica at a time, aborting below ``min_healthy``;
- THE chaos test: a 3-replica fleet under a live read hammer survives
  ``replica_kill``, ``replica_slow`` (breaker open→half-open→close,
  router p99 bounded) and a full rolling reload with ZERO failed reads
  and ZERO mixed-version responses;
- the /reload-vs-inflight-delta race on a single server: a delta racing
  an unseen external publish REBASES onto it instead of clobbering it
  (the contract the fleet prober's reload cadence leans on);
- serve_cli client-side resilience: bounded decorrelated-jitter retries
  honoring Retry-After, ``--deadline-ms`` → ``X-Deadline-Ms``.
"""

import json
import os
import threading
import time
import urllib.error
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np
import pytest

from graphmine_tpu.graph.container import build_graph
from graphmine_tpu.obs.schema import validate_records
from graphmine_tpu.obs.spans import Tracer
from graphmine_tpu.pipeline.checkpoint import graph_fingerprint
from graphmine_tpu.pipeline.metrics import MetricsSink
from graphmine_tpu.serve import (
    DeltaIngestor,
    EdgeDelta,
    SnapshotStore,
)
from graphmine_tpu.serve.delta import cold_recompute
from graphmine_tpu.serve.fleet import (
    BREAKER_CLOSED,
    BREAKER_HALF_OPEN,
    BREAKER_OPEN,
    DEGRADED,
    DOWN,
    DRAINING,
    HEALTHY,
    JOINING,
    CircuitBreaker,
    FleetConfig,
    FleetRouter,
    ReplicaSet,
    ReplicaSpec,
)
from graphmine_tpu.serve.server import SnapshotServer
from graphmine_tpu.testing import faults

pytestmark = pytest.mark.fleet


# ---- fixtures -------------------------------------------------------------


def _clique(lo, hi):
    ids = np.arange(lo, hi)
    s, d = np.meshgrid(ids, ids)
    m = s.ravel() < d.ravel()
    return s.ravel()[m], d.ravel()[m]


def _community_graph():
    parts = [_clique(0, 12), _clique(12, 26), _clique(26, 40)]
    src = np.concatenate([p[0] for p in parts]).astype(np.int32)
    dst = np.concatenate([p[1] for p in parts]).astype(np.int32)
    return src, dst, 40


def _sink():
    return MetricsSink(tracer=Tracer())


def _publish_base(tmp_path, sink=None):
    src, dst, v = _community_graph()
    g = build_graph(src, dst, num_vertices=v)
    labels, cc, _ = cold_recompute(g)
    store = SnapshotStore(str(tmp_path / "snap"))
    store.publish(
        {
            "src": src, "dst": dst, "labels": labels, "cc_labels": cc,
            "lof": np.zeros(v, np.float32),
        },
        fingerprint=graph_fingerprint(src, dst),
        sink=sink,
    )
    return store, src, dst, v


def _post(host, port, path, payload, timeout=60, headers=None):
    req = urllib.request.Request(
        f"http://{host}:{port}{path}",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json", **(headers or {})},
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read()), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read()), dict(e.headers)


def _get(host, port, path, timeout=30):
    with urllib.request.urlopen(
        f"http://{host}:{port}{path}", timeout=timeout
    ) as r:
        return json.loads(r.read())


def _fast_config(**overrides):
    """A CPU-test FleetConfig: tight probe cadence, short data-plane
    timeout, quick breaker backoff — everything the chaos clock needs
    to converge in seconds instead of minutes."""
    kv = dict(
        probe_interval_s=0.08,
        probe_timeout_s=4.0,
        read_timeout_s=0.4,
        down_after_probes=2,
        reload_cadence_s=0.1,
        rejoin_timeout_s=15.0,
        breaker_window=6,
        breaker_open_failures=3,
        breaker_open_rate=0.5,
        breaker_backoff_base_s=0.3,
        breaker_backoff_max_s=1.0,
        retry_after_s=1.0,
        default_deadline_ms=5000,
    )
    kv.update(overrides)
    return FleetConfig(**kv)


class _Fleet:
    """One in-process 3-replica fleet + router, for the HTTP tests.
    Each replica is a real SnapshotServer on its own port — the router
    genuinely speaks HTTP to them."""

    def __init__(self, store, n=3, config=None, sink=None,
                 start_prober=True):
        self.store = store
        self.sink = sink
        self.servers = [SnapshotServer(store) for _ in range(n)]
        self.addrs = [s.start() for s in self.servers]
        self.specs = [
            ReplicaSpec(f"r{i}", h, p) for i, (h, p) in enumerate(self.addrs)
        ]
        self.config = config if config is not None else _fast_config()
        self.router = FleetRouter(
            self.specs, writer="r0", sink=sink, config=self.config,
        )
        if start_prober:
            self.host, self.port = self.router.start()
        else:
            # no HTTP router / prober thread: tests drive probe_once()
            self.host = self.port = None

    def wait_committed(self, version=None, timeout=10.0):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            c = self.router.replica_set.committed_version()
            if c is not None and (version is None or c >= version):
                return c
            time.sleep(0.02)
        raise AssertionError(
            f"fleet never committed "
            f"{'any version' if version is None else f'v{version}'} "
            f"(state: {self.router.replica_set.snapshot()})"
        )

    def restart_replica(self, i):
        """'Restart the process': a fresh SnapshotServer on the same
        port (the spec's address is the replica's identity). The bind
        retries briefly — under a full-suite run another socket can
        transiently hold the freed ephemeral port (an outgoing
        connection's tuple in TIME_WAIT), exactly like a real restart
        racing the OS."""
        host, port = self.addrs[i]
        self.servers[i] = SnapshotServer(self.store, host=host, port=port)
        deadline = time.monotonic() + 10.0
        while True:
            try:
                self.servers[i].start()
                break
            except OSError:
                if time.monotonic() >= deadline:
                    raise
                time.sleep(0.2)
        return self.servers[i]

    def stop(self):
        self.router.stop()
        for s in self.servers:
            try:
                s.stop()
            except Exception:  # noqa: BLE001 — killed replicas
                pass


# ---- circuit breaker unit -------------------------------------------------


def test_breaker_open_half_open_close():
    """The full episode: rate threshold opens, backoff gates the
    half-open probe, one clean probe closes — every transition fired."""
    from graphmine_tpu.pipeline.resilience import ResilienceConfig

    now = [100.0]
    seen = []
    b = CircuitBreaker(
        "r1", window=6, open_failures=3, open_rate=0.5,
        backoff=ResilienceConfig(backoff_base_s=0.5, backoff_max_s=4.0),
        on_transition=lambda f, t, r: seen.append((f, t, r)),
        clock=lambda: now[0],
    )
    assert b.allow_request() and b.state == BREAKER_CLOSED
    b.record_failure("timeout 1")
    b.record_failure("timeout 2")
    assert b.state == BREAKER_CLOSED  # below the count threshold
    b.record_failure("timeout 3")
    assert b.state == BREAKER_OPEN and not b.allow_request()
    assert seen[-1][0] == BREAKER_CLOSED and seen[-1][1] == BREAKER_OPEN
    assert "3 failures" in seen[-1][2]
    # not due until the backoff elapses
    assert not b.probe_due()
    now[0] += 10.0
    assert b.probe_due()
    assert b.state == BREAKER_HALF_OPEN and not b.allow_request()
    assert not b.probe_due()  # one probe granted per episode
    # failed probe -> re-open with a LONGER backoff (attempt 2)
    b.probe_result(False, "still slow")
    assert b.state == BREAKER_OPEN
    snap = b.snapshot()
    assert snap["open_episodes"] == 2
    now[0] += 10.0
    assert b.probe_due()
    b.probe_result(True, "answered fast")
    assert b.state == BREAKER_CLOSED and b.allow_request()
    # escalation memory: a probe-close DECAYS the episode counter (2->1)
    # rather than zeroing it, so a flapping replica re-opens with a
    # longer backoff; only a full clean window resets it
    assert b.snapshot()["open_episodes"] == 1
    for _ in range(6):  # window=6 of straight successes
        b.record_success()
    assert b.snapshot()["open_episodes"] == 0
    states = [(f, t) for f, t, _ in seen]
    assert states == [
        (BREAKER_CLOSED, BREAKER_OPEN),
        (BREAKER_OPEN, BREAKER_HALF_OPEN),
        (BREAKER_HALF_OPEN, BREAKER_OPEN),
        (BREAKER_OPEN, BREAKER_HALF_OPEN),
        (BREAKER_HALF_OPEN, BREAKER_CLOSED),
    ]


def test_breaker_rate_threshold_needs_rate_and_count():
    """Interleaved successes keep the failure RATE below the bar: no
    open, even past the absolute failure count."""
    b = CircuitBreaker("r1", window=8, open_failures=3, open_rate=0.5)
    for _ in range(3):
        b.record_success()
        b.record_success()
        b.record_failure("blip")
    assert b.state == BREAKER_CLOSED  # 3 failures but rate 3/8 < 0.5


# ---- committed version / quorum -------------------------------------------


def _manual_set(versions_states, writer="a"):
    specs = [ReplicaSpec(chr(ord("a") + i), "h", i) for i in
             range(len(versions_states))]
    rs = ReplicaSet(specs, writer=writer, config=_fast_config())
    for spec, (version, state) in zip(specs, versions_states):
        rep = rs.replica(spec.id)
        rep.version = version
        rep.state = state
    rs._recompute()
    return rs


def test_committed_version_is_quorum_max_and_monotonic():
    """Committed = max version held by a read quorum; DOWN replicas
    hold nothing; quorum loss never rolls it backwards."""
    rs = _manual_set([(1, HEALTHY), (1, HEALTHY), (1, HEALTHY)])
    assert rs.quorum == 2 and rs.committed_version() == 1
    # one replica ahead: quorum still at 1
    rs = _manual_set([(2, HEALTHY), (1, HEALTHY), (1, HEALTHY)])
    assert rs.committed_version() == 1
    # two ahead: committed advances
    rs = _manual_set([(2, HEALTHY), (2, HEALTHY), (1, HEALTHY)])
    assert rs.committed_version() == 2
    # a DOWN replica's version doesn't count toward quorum
    rs = _manual_set([(2, HEALTHY), (2, DOWN), (1, HEALTHY)])
    assert rs.committed_version() == 1
    # monotonic: losing quorum keeps the last committed (unavailable-
    # consistent), never time-travels
    rs = _manual_set([(2, HEALTHY), (2, HEALTHY), (1, HEALTHY)])
    assert rs.committed_version() == 2
    rs.replica("a").state = DOWN
    rs.replica("b").state = DOWN
    rs._recompute()
    assert rs.committed_version() == 2
    # and pick() finds nothing at v2 -> the router 503s rather than
    # serving v1 to a session that has seen v2
    assert rs.pick(2) is None


def test_pick_prefers_healthy_skips_breakers_and_wrong_versions():
    rs = _manual_set([(1, HEALTHY), (1, DEGRADED), (2, HEALTHY)])
    picks = {rs.pick(1).spec.id for _ in range(8)}
    assert picks == {"a"}  # healthy preferred over degraded; c is at v2
    # exclude the healthy one -> the degraded replica is the fallback
    assert rs.pick(1, exclude=("a",)).spec.id == "b"
    # an open breaker removes eligibility entirely
    for _ in range(6):
        rs.replica("a").breaker.record_failure("x")
    assert rs.replica("a").breaker.state == BREAKER_OPEN
    assert rs.pick(1).spec.id == "b"


# ---- router HTTP: consistent-version routing ------------------------------


def test_router_consistent_version_routing_and_pin_echo(tmp_path):
    """Reads serve exactly the committed version with an
    X-Pinned-Version echo; committed advances only when a quorum holds
    the new version; a session pinned AHEAD of the fleet is refused
    rather than handed an older version."""
    sink = _sink()
    store, *_ = _publish_base(tmp_path)
    fleet = _Fleet(store, sink=sink)
    try:
        assert fleet.wait_committed() == 1
        code, body, headers = _post(
            fleet.host, fleet.port, "/query", {"vertices": [0, 13, 27]}
        )
        assert code == 200 and body["version"] == 1
        assert headers["X-Pinned-Version"] == "1"
        assert headers["X-Fleet-Replica"] in {"r0", "r1", "r2"}

        # external publish v2 + ONE replica reloads: quorum still at v1
        ext = DeltaIngestor(store, lof_k=4, check_samples=8)
        ext.apply(EdgeDelta.from_pairs(insert=[(40, 12), (40, 13)]))
        h1, p1 = fleet.addrs[1]
        assert _post(h1, p1, "/reload", {})[1]["swapped"] is True
        time.sleep(0.3)  # several probe passes
        assert fleet.router.replica_set.committed_version() == 1
        for _ in range(6):
            code, body, headers = _post(
                fleet.host, fleet.port, "/query", {"vertices": [0]}
            )
            assert code == 200
            assert body["version"] == 1 == int(headers["X-Pinned-Version"])

        # second replica reloads -> quorum at v2 -> committed advances
        h2, p2 = fleet.addrs[2]
        _post(h2, p2, "/reload", {})
        fleet.wait_committed(2)
        code, body, headers = _post(
            fleet.host, fleet.port, "/query", {"vertices": [40]}
        )
        assert code == 200
        assert body["version"] == 2 == int(headers["X-Pinned-Version"])
        # a stale session pin (<= committed) is fine: monotonic reads
        code, body, _ = _post(
            fleet.host, fleet.port, "/query", {"vertices": [0]},
            headers={"X-Pinned-Version": "1"},
        )
        assert code == 200 and body["version"] == 2
        # a pin AHEAD of the fleet is refused, never downgraded
        code, body, headers = _post(
            fleet.host, fleet.port, "/query", {"vertices": [0]},
            headers={"X-Pinned-Version": "9"},
        )
        assert code == 503 and "pinned v9" in body["reason"]
        assert int(headers["Retry-After"]) >= 1
    finally:
        fleet.stop()
    assert validate_records(sink.records) == []
    served = [
        r for r in sink.records
        if r["phase"] == "fleet_route" and r["verdict"] == "served"
    ]
    assert served and all(r["attempts"] >= 1 for r in served)
    assert any(
        r["phase"] == "fleet_route" and r["verdict"] == "stale_pin"
        for r in sink.records
    )


def test_replica_version_pin_409_on_mismatch(tmp_path):
    """The replica side of the mixed-version guard: an X-Serve-Version
    pin that doesn't match the engine answers 409 (and a matching one
    serves normally)."""
    store, *_ = _publish_base(tmp_path)
    server = SnapshotServer(store)
    host, port = server.start()
    try:
        code, body, _ = _post(
            host, port, "/query", {"vertices": [0]},
            headers={"X-Serve-Version": "1"},
        )
        assert code == 200 and body["version"] == 1
        code, body, _ = _post(
            host, port, "/query", {"vertices": [0]},
            headers={"X-Serve-Version": "7"},
        )
        assert code == 409
        assert body["version"] == 1 and body["requested"] == 7
        assert _get(host, port, "/vertex?v=0")["vertex"] == 0  # unpinned ok
    finally:
        server.stop()


def test_router_retries_onto_live_replica_and_503_when_none(tmp_path):
    """A dead replica mid-rotation costs a retry, not a failed read;
    with every replica dead the router answers 503 + Retry-After inside
    the propagated deadline."""
    sink = _sink()
    store, *_ = _publish_base(tmp_path)
    fleet = _Fleet(store, sink=sink)
    try:
        fleet.wait_committed()
        faults.replica_kill(fleet.servers[2])
        # before the prober can mark it DOWN, reads must still succeed
        # (the router eats the connection error and retries elsewhere)
        for _ in range(6):
            code, body, _ = _post(
                fleet.host, fleet.port, "/query", {"vertices": [0]}
            )
            assert code == 200 and body["version"] == 1
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            if fleet.router.replica_set.replica("r2").state == DOWN:
                break
            time.sleep(0.05)
        assert fleet.router.replica_set.replica("r2").state == DOWN

        faults.replica_kill(fleet.servers[0])
        faults.replica_kill(fleet.servers[1])
        t0 = time.monotonic()
        code, body, headers = _post(
            fleet.host, fleet.port, "/query", {"vertices": [0]},
            headers={"X-Deadline-Ms": "800"},
        )
        elapsed = time.monotonic() - t0
        assert code == 503 and "no eligible replica" in body["reason"]
        assert int(headers["Retry-After"]) >= 1
        assert elapsed < 3.0  # bounded by the deadline, not by timeouts
    finally:
        fleet.stop()
    assert validate_records(sink.records) == []
    assert any(
        r["phase"] == "fleet_route" and r["verdict"] == "no_replica"
        for r in sink.records
    )


def test_stale_replica_never_serves_reads(tmp_path):
    """replica_stale: a version-pinned replica falls behind the fleet
    and silently leaves the read rotation — zero mixed-version answers,
    no error surfaced to readers."""
    store, *_ = _publish_base(tmp_path)
    fleet = _Fleet(store)
    try:
        fleet.wait_committed()
        faults.replica_stale(fleet.servers[2])
        ext = DeltaIngestor(store, lof_k=4, check_samples=8)
        ext.apply(EdgeDelta.from_pairs(insert=[(40, 12)]))
        # roll the other two via their own /reload (writer + r1)
        for i in (0, 1):
            h, p = fleet.addrs[i]
            _post(h, p, "/reload", {})
        fleet.wait_committed(2)
        for _ in range(10):
            code, body, headers = _post(
                fleet.host, fleet.port, "/query", {"vertices": [0]}
            )
            assert code == 200
            assert body["version"] == 2 == int(headers["X-Pinned-Version"])
            assert headers["X-Fleet-Replica"] in {"r0", "r1"}
        assert fleet.servers[2].engine.version == 1  # genuinely stale
    finally:
        fleet.stop()


def test_self_drained_replica_leaves_read_rotation(tmp_path):
    """A replica drained at ITS OWN /drain endpoint (ready: false,
    draining: true) must receive no reads — the prober honors the
    operator's drain instead of demoting it to a still-routable
    degraded state — and rejoins after /undrain."""
    store, *_ = _publish_base(tmp_path)
    fleet = _Fleet(store)

    def wait_state(rid, state, timeout=8.0):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if fleet.router.replica_set.replica(rid).state == state:
                return
            time.sleep(0.03)
        raise AssertionError(
            f"{rid} never reached {state}: "
            f"{fleet.router.replica_set.snapshot()}"
        )

    try:
        fleet.wait_committed()
        h2, p2 = fleet.addrs[2]
        _post(h2, p2, "/drain", {})
        wait_state("r2", DRAINING)
        for _ in range(8):
            code, body, headers = _post(
                fleet.host, fleet.port, "/query", {"vertices": [0]}
            )
            assert code == 200
            assert headers["X-Fleet-Replica"] in {"r0", "r1"}
        _post(h2, p2, "/undrain", {})
        wait_state("r2", HEALTHY)
    finally:
        fleet.stop()


# ---- writer forwarding / read-only ----------------------------------------


def test_writer_forwarding_and_prober_reload_cadence(tmp_path):
    """POST /delta through the router lands on the writer; the prober's
    reload cadence walks the other replicas up to the writer's version
    and committed follows — no client ever sees a mixed version on the
    way."""
    sink = _sink()
    store, *_ = _publish_base(tmp_path)
    fleet = _Fleet(store, sink=sink)
    try:
        fleet.wait_committed()
        code, body, headers = _post(
            fleet.host, fleet.port, "/delta",
            {"insert": [[0, 13], [0, 14]]},
        )
        assert code == 200 and body["version"] == 2
        assert headers["X-Fleet-Replica"] == "r0"
        assert fleet.servers[0].engine.version == 2
        fleet.wait_committed(2)  # the cadence reloaded r1/r2
        assert fleet.servers[1].engine.version == 2
        assert fleet.servers[2].engine.version == 2
        code, body, _ = _post(
            fleet.host, fleet.port, "/query", {"vertices": [0]}
        )
        assert code == 200 and body["version"] == 2
    finally:
        fleet.stop()
    assert validate_records(sink.records) == []
    fwd = [
        r for r in sink.records
        if r["phase"] == "fleet_route" and r["verdict"] == "forwarded"
    ]
    assert fwd and fwd[0]["endpoint"] == "delta"


def test_writer_loss_degrades_to_read_only_and_recovers(tmp_path):
    """Writer down → loud fleet_degraded record, writes 503, reads keep
    serving; the SAME writer returning restores writes (no election)."""
    sink = _sink()
    store, *_ = _publish_base(tmp_path)
    fleet = _Fleet(store, sink=sink)
    try:
        fleet.wait_committed()
        faults.replica_kill(fleet.servers[0])
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline and not fleet.router.replica_set.read_only:
            time.sleep(0.05)
        assert fleet.router.replica_set.read_only
        code, body, headers = _post(
            fleet.host, fleet.port, "/delta", {"insert": [[0, 13]]}
        )
        assert code == 503 and "read-only" in body["reason"]
        assert int(headers["Retry-After"]) >= 1
        # reads still fine at the committed version
        code, body, _ = _post(
            fleet.host, fleet.port, "/query", {"vertices": [0]}
        )
        assert code == 200 and body["version"] == 1
        # router healthz says read_only; fleetz shows the writer down
        h = _get(fleet.host, fleet.port, "/healthz")
        assert h["read_only"] is True and h["ok"] is True
        fz = _get(fleet.host, fleet.port, "/fleetz")
        writer_row = next(r for r in fz["replicas"] if r["writer"])
        assert writer_row["state"] == DOWN

        fleet.restart_replica(0)
        deadline = time.monotonic() + 8
        while time.monotonic() < deadline and fleet.router.replica_set.read_only:
            time.sleep(0.05)
        assert not fleet.router.replica_set.read_only
        code, body, _ = _post(
            fleet.host, fleet.port, "/delta", {"insert": [[0, 15]]}
        )
        assert code == 200 and body["version"] == 2
    finally:
        fleet.stop()
    assert validate_records(sink.records) == []
    flips = [r for r in sink.records if r["phase"] == "fleet_degraded"]
    assert [r["read_only"] for r in flips] == [True, False]
    assert "split-brain" in flips[0]["reason"]


# ---- rolling reload -------------------------------------------------------


def test_rolling_reload_walks_fleet_to_new_version(tmp_path):
    """An external publish + /roll takes every replica (writer last)
    through drain → reload → rejoin; committed lands on the new
    version."""
    sink = _sink()
    store, *_ = _publish_base(tmp_path)
    fleet = _Fleet(store, sink=sink)
    try:
        fleet.wait_committed()
        ext = DeltaIngestor(store, lof_k=4, check_samples=8)
        ext.apply(EdgeDelta.from_pairs(insert=[(40, 12), (40, 13)]))
        code, out, _ = _post(fleet.host, fleet.port, "/roll", {})
        assert code == 200 and out["ok"], out
        assert [r["version"] for r in out["rolled"]] == [2, 2, 2]
        # writer rolls LAST
        assert out["rolled"][-1]["id"] == "r0"
        assert out["committed_version"] == 2
        for s in fleet.servers:
            assert s.engine.version == 2
        code, body, _ = _post(
            fleet.host, fleet.port, "/query", {"vertices": [40]}
        )
        assert code == 200 and body["version"] == 2
    finally:
        fleet.stop()
    assert validate_records(sink.records) == []
    # drain/rejoin transitions were recorded per replica
    health = [r for r in sink.records if r["phase"] == "replica_health"]
    assert sum(1 for r in health if r["to_state"] == DRAINING) == 3
    assert sum(
        1 for r in health
        if r["from_state"] == DRAINING and r["to_state"] == HEALTHY
    ) == 3


def test_rolling_reload_aborts_below_min_healthy(tmp_path):
    """With min_healthy == replica count, draining anyone would dip
    below the floor: the roll refuses up front and leaves every replica
    serving."""
    store, *_ = _publish_base(tmp_path)
    fleet = _Fleet(store, config=_fast_config(min_healthy=3))
    try:
        fleet.wait_committed()
        code, out, _ = _post(fleet.host, fleet.port, "/roll", {})
        assert code == 409 and not out["ok"]
        assert "min_healthy" in out["aborted"]
        assert out["rolled"] == []
        states = {
            r["id"]: r["state"]
            for r in fleet.router.fleetz()["replicas"]
        }
        assert set(states.values()) == {HEALTHY}
    finally:
        fleet.stop()


# ---- the /reload-vs-inflight-delta rebase (satellite) ---------------------


def test_delta_rebases_onto_unseen_external_publish(tmp_path):
    """The r7 contract pinned under the fleet prober's reload cadence:
    a delta whose apply races ahead of /reload must REBASE onto the
    store's newest (externally published) snapshot, not clobber it by
    chaining a version on top of the stale served state."""
    sink = _sink()
    store, src, dst, v = _publish_base(tmp_path, sink=sink)
    server = SnapshotServer(store, sink=sink)
    host, port = server.start()
    try:
        # external publish v2 lands; the server still serves v1 and no
        # /reload has fired (the prober hasn't gotten there yet)
        ext = DeltaIngestor(store, lof_k=4, check_samples=8)
        ext.apply(EdgeDelta.from_pairs(insert=[(v, 0), (v, 1)]))
        assert server.engine.version == 1
        # a delta arrives FIRST: its apply must rebase onto v2
        code, out, _ = _post(host, port, "/delta", {"insert": [[0, 13]]})
        assert code == 200 and out["version"] == 3
        eng = server.engine
        edges = set(
            zip(np.asarray(eng.snapshot["src"]).tolist(),
                np.asarray(eng.snapshot["dst"]).tolist())
        )
        assert (v, 0) in edges and (v, 1) in edges  # external kept
        assert (0, 13) in edges                     # delta applied
        assert _get(host, port, "/vertex?v=40")["label"] == 0
    finally:
        server.stop()
    assert validate_records(sink.records) == []


def test_reload_during_held_apply_then_queued_delta(tmp_path):
    """The interleaving the prober's cadence produces: a /reload lands
    while the apply worker is mid-publish with another batch queued
    behind it — nothing is lost, versions chain, and the queued batch
    builds on everything before it."""
    sink = _sink()
    store, src, dst, v = _publish_base(tmp_path, sink=sink)
    server = SnapshotServer(store, sink=sink)
    host, port = server.start()
    results, reloads = [], []
    inj = faults.FaultInjector()
    inj.add("delta_repair", faults.slow_repair(0.8), at=1, repeat=1)

    def fire(payload):
        results.append(_post(host, port, "/delta", payload))

    try:
        with inj.installed():
            t0 = threading.Thread(target=fire, args=({"insert": [[0, 13]]},))
            t0.start()
            time.sleep(0.25)  # batch A mid-apply, holding the lock
            t1 = threading.Thread(target=fire, args=({"insert": [[0, 14]]},))
            t1.start()
            time.sleep(0.1)   # batch B queued behind A
            # the prober-cadence reload, racing both
            reloads.append(_post(host, port, "/reload", {}))
            t0.join(timeout=60)
            t1.join(timeout=60)
        assert [r[0] for r in results] == [200, 200]
        versions = sorted(r[1]["version"] for r in results)
        assert versions == [2, 3]
        eng = server.engine
        assert eng.version == 3
        edges = set(
            zip(np.asarray(eng.snapshot["src"]).tolist(),
                np.asarray(eng.snapshot["dst"]).tolist())
        )
        assert (0, 13) in edges and (0, 14) in edges
        assert reloads[0][0] == 200
    finally:
        server.stop()
    assert validate_records(sink.records) == []


# ---- liveness vs readiness (satellite) ------------------------------------


def test_healthz_ready_vs_ok(tmp_path):
    """The liveness/readiness split: ok stays true (alive) while ready
    flips false on drain or a stale-beyond-bound snapshot."""
    store, *_ = _publish_base(tmp_path)
    server = SnapshotServer(store)
    host, port = server.start()
    try:
        h = _get(host, port, "/healthz")
        assert h["ok"] is True and h["ready"] is True
        assert h["draining"] is False
        code, h, _ = _post(host, port, "/drain", {})
        assert code == 200 and h["ready"] is False and h["ok"] is True
        assert h["not_ready_reason"] == "draining"
        code, h, _ = _post(host, port, "/undrain", {})
        assert h["ready"] is True
    finally:
        server.stop()


def test_healthz_ready_false_when_stale_beyond_bound(tmp_path):
    store, *_ = _publish_base(tmp_path)
    server = SnapshotServer(store, ready_max_age_s=1e-6)
    host, port = server.start()
    try:
        h = _get(host, port, "/healthz")
        assert h["ok"] is True and h["ready"] is False
        assert "snapshot_age" in h["not_ready_reason"]
    finally:
        server.stop()


def test_ready_max_age_env(tmp_path, monkeypatch):
    monkeypatch.setenv("GRAPHMINE_READY_MAX_AGE_S", "123.5")
    store, *_ = _publish_base(tmp_path)
    server = SnapshotServer(store)
    assert server.ready_max_age_s == 123.5
    monkeypatch.setenv("GRAPHMINE_READY_MAX_AGE_S", "soon")
    with pytest.raises(ValueError, match="GRAPHMINE_READY_MAX_AGE_S"):
        SnapshotServer(store)


def test_delta_deadline_header_narrows_budget(tmp_path):
    """X-Deadline-Ms end-to-end on a single server: a queued batch past
    the client's (smaller) budget sheds with the structured 503."""
    sink = _sink()
    store, *_ = _publish_base(tmp_path, sink=sink)
    server = SnapshotServer(store, sink=sink)
    host, port = server.start()
    inj = faults.FaultInjector()
    inj.add("delta_repair", faults.slow_repair(1.2), at=1, repeat=1)
    results = []

    def fire(payload, headers=None):
        results.append(
            _post(host, port, "/delta", payload, headers=headers)
        )

    try:
        with inj.installed():
            t0 = threading.Thread(target=fire, args=({"insert": [[0, 13]]},))
            t0.start()
            time.sleep(0.3)  # slow apply in flight
            t1 = threading.Thread(
                target=fire,
                args=({"insert": [[0, 14]]},),
                kwargs={"headers": {"X-Deadline-Ms": "400"}},
            )
            t1.start()
            t0.join(timeout=60)
            t1.join(timeout=60)
        codes = sorted(r[0] for r in results)
        assert codes == [200, 503]
        shed = next(r for r in results if r[0] == 503)
        assert "deadline 0.4s" in shed[1]["reason"]
    finally:
        server.stop()
    assert validate_records(sink.records) == []


# ---- serve_cli client-side resilience (satellite) -------------------------


class _FlakyHandler(BaseHTTPRequestHandler):
    """Stub server: sheds the first N POSTs with 503 + Retry-After,
    then answers 200 — recording every request's X-Deadline-Ms."""

    sheds_left = 0
    retry_after = "1"
    seen_deadlines: list = []

    def log_message(self, fmt, *args):  # noqa: A003
        pass

    def do_POST(self):  # noqa: N802
        self.rfile.read(int(self.headers.get("Content-Length", 0)))
        type(self).seen_deadlines.append(
            self.headers.get("X-Deadline-Ms")
        )
        if type(self).sheds_left > 0:
            type(self).sheds_left -= 1
            body = json.dumps({"verdict": "shed", "reason": "test"}).encode()
            self.send_response(503)
            self.send_header("Retry-After", type(self).retry_after)
        else:
            body = json.dumps({"version": 2}).encode()
            self.send_response(200)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)


def _stub_server(sheds, retry_after="1"):
    class H(_FlakyHandler):
        sheds_left = sheds
        seen_deadlines = []
    H.retry_after = retry_after
    httpd = ThreadingHTTPServer(("127.0.0.1", 0), H)
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    host, port = httpd.server_address[:2]
    return httpd, H, f"http://{host}:{port}"


def test_serve_cli_retries_honor_retry_after():
    import sys
    sys.path.insert(
        0, os.path.join(os.path.dirname(__file__), "..", "tools")
    )
    import serve_cli

    httpd, H, url = _stub_server(sheds=2, retry_after="3")
    slept = []
    try:
        out = serve_cli.request_with_retries(
            f"{url}/delta", {"insert": [[1, 2]]}, max_retries=4,
            sleep=slept.append,
        )
    finally:
        httpd.shutdown()
        httpd.server_close()
    assert out["status"] == 200 and out["attempts"] == 3
    assert out["body"]["version"] == 2
    # every backoff obeyed the server's Retry-After floor
    assert len(slept) == 2 and all(s >= 3.0 for s in slept)


def test_serve_cli_deadline_bounds_retries_and_propagates():
    import sys
    sys.path.insert(
        0, os.path.join(os.path.dirname(__file__), "..", "tools")
    )
    import serve_cli

    httpd, H, url = _stub_server(sheds=100, retry_after="1")

    def sleeper(s):
        time.sleep(min(s, 0.2))

    try:
        t0 = time.monotonic()
        out = serve_cli.request_with_retries(
            f"{url}/delta", {"insert": [[1, 2]]}, deadline_ms=600,
            max_retries=50, sleep=sleeper,
        )
        elapsed = time.monotonic() - t0
    finally:
        httpd.shutdown()
        httpd.server_close()
    assert out["status"] == 503
    assert elapsed < 5.0  # the deadline stopped the retry loop
    # the budget rode every attempt, shrinking
    deadlines = [int(d) for d in H.seen_deadlines if d]
    assert deadlines and deadlines == sorted(deadlines, reverse=True)
    assert deadlines[0] <= 600


def test_serve_cli_exhausts_retries_with_jitter_backoff():
    import sys
    sys.path.insert(
        0, os.path.join(os.path.dirname(__file__), "..", "tools")
    )
    import serve_cli

    httpd, H, url = _stub_server(sheds=100, retry_after="")
    slept = []
    try:
        out = serve_cli.request_with_retries(
            f"{url}/delta", {}, max_retries=3, sleep=slept.append,
        )
    finally:
        httpd.shutdown()
        httpd.server_close()
    assert out["status"] == 503 and out["attempts"] == 4
    assert len(slept) == 3
    assert all(s > 0 for s in slept)


# ---- THE fleet chaos acceptance test --------------------------------------


def test_fleet_chaos_kill_slow_roll(tmp_path):
    """ISSUE 9 acceptance: a 3-replica fleet under a live read hammer
    survives (a) replica_slow on r1 — breaker open → half-open → close,
    router p99 bounded while the replica crawls; (b) replica_kill of r2
    + restart — reads never fail while it is dead, it rejoins after;
    (c) a full rolling reload to an externally published snapshot
    version; (d) writer kill — loud fleet_degraded, fleet serves
    read-only. Throughout: ZERO failed client reads and ZERO
    mixed-version responses (every body's version equals its
    X-Pinned-Version echo, monotonic per client)."""
    sink = _sink()
    store, src, dst, v = _publish_base(tmp_path)
    fleet = _Fleet(store, sink=sink)
    hammer_errors: list = []
    lat_lock = threading.Lock()
    latencies: list = []
    per_thread_versions: dict = {}
    stop = threading.Event()

    def hammer(tid):
        seen = per_thread_versions.setdefault(tid, [])
        while not stop.is_set():
            t0 = time.perf_counter()
            try:
                code, body, headers = _post(
                    fleet.host, fleet.port, "/query",
                    {"vertices": [0, 13, 27]}, timeout=30,
                )
                dt = time.perf_counter() - t0
                if code != 200:
                    raise AssertionError(
                        f"read failed: HTTP {code} {body}"
                    )
                if body["version"] != int(headers["X-Pinned-Version"]):
                    raise AssertionError(
                        f"MIXED VERSION: body v{body['version']} != pin "
                        f"{headers['X-Pinned-Version']}"
                    )
                if len(body["label"]) != 3:
                    raise AssertionError(f"torn body: {body}")
                seen.append(body["version"])
                with lat_lock:
                    latencies.append(dt)
            except Exception as e:  # noqa: BLE001 — collect, assert later
                hammer_errors.append(e)
                return
            time.sleep(0.01)

    def wait_breaker(state, timeout=12.0):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if fleet.router.replica_set.replica("r1").breaker.state == state:
                return
            time.sleep(0.03)
        raise AssertionError(
            f"breaker never reached {state}: "
            f"{fleet.router.replica_set.replica('r1').breaker.snapshot()}"
        )

    threads = [
        threading.Thread(target=hammer, args=(i,)) for i in range(3)
    ]
    try:
        fleet.wait_committed()
        for t in threads:
            t.start()
        time.sleep(0.5)  # steady-state reads before any chaos

        # (a) SLOW: r1 crawls at 1.5s/request; the router's 0.4s read
        # timeout turns every attempt into a breaker failure while the
        # generous 4s probe keeps the replica "alive" — exactly the
        # split the breaker exists for.
        faults.replica_slow(fleet.servers[1], 1.5)
        wait_breaker(BREAKER_OPEN)
        # while open, reads keep flowing off the healthy replicas
        time.sleep(0.6)
        faults.replica_slow(fleet.servers[1], 0.0)  # heal
        wait_breaker(BREAKER_CLOSED, timeout=15.0)

        # (b) KILL r2, serve through it, restart, rejoin
        faults.replica_kill(fleet.servers[2])
        deadline = time.monotonic() + 6
        while time.monotonic() < deadline:
            if fleet.router.replica_set.replica("r2").state == DOWN:
                break
            time.sleep(0.05)
        assert fleet.router.replica_set.replica("r2").state == DOWN
        time.sleep(0.4)  # reads continue on 2 replicas
        fleet.restart_replica(2)
        deadline = time.monotonic() + 8
        while time.monotonic() < deadline:
            if fleet.router.replica_set.replica("r2").state == HEALTHY:
                break
            time.sleep(0.05)
        assert fleet.router.replica_set.replica("r2").state == HEALTHY

        # (c) ROLLING RELOAD to an externally published v2, hammer live
        ext = DeltaIngestor(store, lof_k=4, check_samples=8)
        ext.apply(EdgeDelta.from_pairs(insert=[(v, 12), (v, 13)]))
        code, out, _ = _post(fleet.host, fleet.port, "/roll", {},
                             timeout=120)
        assert code == 200 and out["ok"], out
        assert out["committed_version"] == 2
        time.sleep(0.4)  # reads at v2

        # (d) WRITER KILL: read-only fleet, loud record, reads keep going
        faults.replica_kill(fleet.servers[0])
        deadline = time.monotonic() + 6
        while time.monotonic() < deadline and not fleet.router.replica_set.read_only:
            time.sleep(0.05)
        assert fleet.router.replica_set.read_only
        code, body, _ = _post(
            fleet.host, fleet.port, "/delta", {"insert": [[0, 13]]}
        )
        assert code == 503 and "read-only" in body["reason"]
        time.sleep(0.4)

        stop.set()
        for t in threads:
            t.join(timeout=30)

        # ZERO failed reads, ZERO mixed versions (checked in-loop),
        # versions monotonic per client session
        assert hammer_errors == [], hammer_errors[:3]
        total_reads = sum(len(vs) for vs in per_thread_versions.values())
        assert total_reads > 50
        for tid, vs in per_thread_versions.items():
            assert vs == sorted(vs), f"thread {tid} saw versions go back"
            assert set(vs) <= {1, 2}
        assert any(2 in set(vs) for vs in per_thread_versions.values())

        # p99 bounded: even through the slow phase, the breaker +
        # bounded retry kept the tail under the slow replica's 1.5s
        # crawl (one timed-out attempt + a fast retry, not a pile-up)
        lat = sorted(latencies)
        p99 = lat[min(len(lat) - 1, int(0.99 * len(lat)))]
        assert p99 < 1.5, f"router p99 {p99:.3f}s not bounded"

        # breaker episode fully observed
        transitions = [
            (r["from_state"], r["to_state"])
            for r in sink.records
            if r["phase"] == "breaker_transition" and r["replica"] == "r1"
        ]
        assert (BREAKER_CLOSED, BREAKER_OPEN) in transitions
        assert (BREAKER_OPEN, BREAKER_HALF_OPEN) in transitions
        assert (BREAKER_HALF_OPEN, BREAKER_CLOSED) in transitions

        # writer loss was loud
        flips = [r for r in sink.records if r["phase"] == "fleet_degraded"]
        assert flips and flips[-1]["read_only"] is True

        # replica lifecycle visible: r2 died and rejoined
        r2_states = [
            (r["from_state"], r["to_state"])
            for r in sink.records
            if r["phase"] == "replica_health" and r["replica"] == "r2"
        ]
        assert (HEALTHY, DOWN) in r2_states or (DEGRADED, DOWN) in r2_states
        assert (DOWN, JOINING) in r2_states
        assert (JOINING, HEALTHY) in r2_states
    finally:
        stop.set()
        fleet.stop()
    assert validate_records(sink.records) == []

    # the offline report renders the fleet section from the JSONL alone
    import sys
    sys.path.insert(
        0, os.path.join(os.path.dirname(__file__), "..", "tools")
    )
    import obs_report

    report = obs_report.build_report(sink.records)
    assert "-- fleet (replica health / breakers / routing) --" in report
    assert "breaker timeline:" in report
    assert "FLEET READ-ONLY" in report
    assert "route verdicts:" in report


# ---- fleet_cli (multi-process smoke) --------------------------------------


def test_fleet_cli_up_multiprocess_smoke(tmp_path):
    """The first multi-process path in the tree: fleet_cli spawns real
    replica PROCESSES (serve_cli serve, one port each) + the router,
    and a client query round-trips through the whole stack."""
    import socket
    import subprocess
    import sys

    store, *_ = _publish_base(tmp_path)

    def free_port():
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    def free_port_pair():
        """Replica i listens on base + i, seconds from now. A port the
        kernel hands out for port 0 has a neighbour the next port-0 bind
        of any other test worker may take first (seen under xdist:
        `Address already in use` on base + 1), so the pair is drawn
        below the ephemeral range, where nobody binds port 0."""
        rng = np.random.default_rng(os.getpid())
        for base in rng.integers(20000, 30000, 64).tolist():
            try:
                with socket.socket() as a, socket.socket() as b:
                    a.bind(("127.0.0.1", base))
                    b.bind(("127.0.0.1", base + 1))
                return base
            except OSError:
                continue
        raise RuntimeError("no free port pair")

    router_port, base_port = free_port(), free_port_pair()
    repo = os.path.join(os.path.dirname(__file__), "..")
    proc = subprocess.Popen(
        [
            sys.executable, os.path.join(repo, "tools", "fleet_cli.py"),
            "up", "--store", str(tmp_path / "snap"), "--replicas", "2",
            "--port", str(router_port),
            "--replica-base-port", str(base_port),
        ],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    try:
        deadline = time.monotonic() + 120
        ready = False
        while time.monotonic() < deadline:
            try:
                h = _get("127.0.0.1", router_port, "/healthz", timeout=2)
                if h.get("ready"):
                    ready = True
                    break
            except Exception:  # noqa: BLE001 — still starting
                pass
            time.sleep(0.5)
        assert ready, "fleet never became ready"
        code, body, headers = _post(
            "127.0.0.1", router_port, "/query", {"vertices": [0, 13]}
        )
        assert code == 200 and body["version"] == 1
        assert headers["X-Pinned-Version"] == "1"
    finally:
        proc.terminate()
        proc.wait(timeout=30)
