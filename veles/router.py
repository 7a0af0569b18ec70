"""Observability-actuated fleet control: ``velescli route``.

ROADMAP item 2 / ISSUE 13: one front address in front of N serving
replicas, with every routing, failover and scaling decision MADE FROM
the observability plane the previous PRs built — and the decision
loop itself fully observable.

Three cooperating pieces, one process:

* :class:`FleetController` — the sensor-to-decision loop. A daemon
  thread reuses ``veles/fleet.py``'s scraper (parallel, per-target
  time-bounded) to maintain a fleet snapshot per tick: readiness,
  firing SLO burn-rate alerts, queue-depth gauges, KV occupancy.
  Policy per backend:

  - **eager failover** — a replica is EJECTED the moment its
    ``/readyz`` flips, its SLO burn-rate fires, its scrape times out,
    or the proxy path records ``eject_failures`` consecutive
    transport errors. Ejection is an event (``router_failover`` in
    ``/debug/events``), a counter
    (``veles_router_ejections_total{reason}``) and a log line —
    never a silent state flip;
  - **half-open re-admission** — when an ejected replica's scrape
    turns healthy again it becomes HALF-OPEN (mirroring the snapshot
    store's circuit breaker): exactly ONE live request is routed
    there as the probe; success re-admits (``router_readmit``
    event), failure re-ejects. Operators can also DRAIN a replica
    (``POST /router/drain``): no new requests, in-flight ones
    finish — the zero-downtime rollout primitive.

* :class:`RouterFrontend` — the reactor-hosted HTTP proxy. Inline
  routes (probes, metrics, ``/router/status``) answer from cached
  state on the loop; each proxied ``/v1/*`` request runs on a worker
  thread (the same discipline as the serving frontend's blocking
  routes). Routing policy: **least-queue** (scraped queue-depth
  gauge + live router-side inflight) with **consistent-hash
  stickiness** for ``/v1/generate`` requests that carry a session
  key (``x-veles-session`` header or ``"session"`` body field) — a
  session keeps hitting the same replica's KV/prefix locality, and
  an ejection only remaps the ejected replica's key range (ring
  lookup skips ineligible backends; survivors' keys never move).
  In-flight streams are never re-routed: ejection only steers NEW
  requests. The proxy propagates ``traceparent`` (one hop-child per
  forward), so one trace spans client -> router -> replica; every
  routed request lands in ``veles_router_requests_total
  {replica,outcome}`` and the ``veles_router_request_seconds``
  latency histogram.

* :class:`Autoscaler` — burn rates and queue trajectories in,
  scale decisions out, through a pluggable EXECUTOR:
  :class:`SubprocessExecutor` really launches/stops replica
  processes (tests, single-host CPU fleets);
  :class:`DryRunExecutor` records decision-only (``--dry-run``; the
  default when no ``--scale-cmd`` is given). Scale-down always
  drains first and stops only at inflight 0. Decisions are
  ``scale_up``/``scale_down`` events in ``/debug/events`` and
  ``veles_router_scale_decisions_total{direction}``.

``velescli top`` renders a router target as its own row (backend
admission states + last autoscale decision) via ``GET
/router/status`` — the same document tests and operators poll.
"""

import argparse
import bisect
import hashlib
import http.client
import json
import os
import shlex
import socket
import subprocess
import sys
import threading
import time
import urllib.request
from concurrent.futures import ThreadPoolExecutor
from urllib.parse import urlsplit

from veles import fleet, health, reactor, telemetry
from veles.logger import Logger
from veles.serving import tenants

#: replica lifecycle states (strings: they land in /router/status)
ADMITTED = "admitted"
EJECTED = "ejected"
HALF_OPEN = "half-open"
DRAINING = "draining"

#: Retry-After hints for router-side 503s: with no backend at all the
#: fleet needs a recovery/scale cycle, not a quick retry
RETRY_AFTER_NO_BACKEND = 5

#: virtual points per backend on the consistent-hash ring — enough
#: spread that one ejection moves ~1/N of the key space, cheap enough
#: to rebuild on membership change
RING_POINTS = 64

#: routing policies (``--routing-policy``): classic least-queue, or
#: latency-aware — weight each admitted backend's scraped serving p99
#: by its current load so a slow replica (brownout, noisy neighbour)
#: sheds share BEFORE it trips an SLO ejection (PR-13 stretch,
#: shipped in ISSUE 18). Backends that predate the p99 scrape fall
#: back to the fleet median, degrading to least-queue behaviour.
ROUTING_POLICIES = ("least-queue", "latency")

_C_REQUESTS = telemetry.LazyChild(lambda: telemetry.counter(
    "veles_router_requests_total",
    "Requests proxied through the router, by chosen replica, "
    "resolved tenant and outcome",
    ("replica", "tenant", "outcome")))


def _resolve_tenant(request):
    """Bounded tenant label for one routed request: the installed
    tenant table's resolver output, or the default tenant with no
    table — raw ``x-veles-tenant`` values never reach a label (zlint
    telemetry-hygiene). The RAW header is still forwarded upstream:
    the replica's own resolution is authoritative."""
    table = tenants.get_table()
    if table is None:
        return tenants.DEFAULT_TENANT
    return table.resolve(request.headers.get("x-veles-tenant"))
_C_EJECT = telemetry.LazyChild(lambda: telemetry.counter(
    "veles_router_ejections_total",
    "Replicas ejected from the routable set, by reason",
    ("reason",)))
_C_SCALE = telemetry.LazyChild(lambda: telemetry.counter(
    "veles_router_scale_decisions_total",
    "Autoscaler decisions emitted, by direction", ("direction",)))
_G_INFLIGHT = telemetry.LazyChild(lambda: telemetry.gauge(
    "veles_router_backend_inflight",
    "Requests currently in flight through the router per backend",
    ("replica",)))
_G_BACKENDS = telemetry.LazyChild(lambda: telemetry.gauge(
    "veles_router_backends",
    "Routable (admitted) backends vs total configured",
    ("state",)))
_H_LATENCY = telemetry.LazyChild(lambda: telemetry.histogram(
    "veles_router_request_seconds",
    "Routed request latency as the router observed it (connect to "
    "last byte)"))
_C_REFRESH = telemetry.LazyChild(lambda: telemetry.counter(
    "veles_router_refreshes_total",
    "Rolling-refresh replica rolls, by outcome", ("outcome",)))


class HashRing:
    """Consistent-hash ring over backend URLs. Lookup walks the ring
    from the key's point and returns the first ELIGIBLE backend, so
    ejecting one replica remaps only its own key range — survivors'
    sessions never move."""

    def __init__(self, urls=()):
        self._points = []            # sorted [(hash, url)]
        for url in urls:
            self.add(url)

    @staticmethod
    def _hash(value):
        return int(hashlib.sha1(
            value.encode("utf-8", "replace")).hexdigest()[:16], 16)

    def add(self, url):
        for i in range(RING_POINTS):
            bisect.insort(self._points,
                          (self._hash("%s#%d" % (url, i)), url))

    def remove(self, url):
        self._points = [p for p in self._points if p[1] != url]

    def lookup(self, key, eligible):
        """First eligible backend clockwise of ``key``'s point."""
        if not self._points or not eligible:
            return None
        idx = bisect.bisect_left(self._points, (self._hash(key), ""))
        n = len(self._points)
        for j in range(n):
            url = self._points[(idx + j) % n][1]
            if url in eligible:
                return url
        return None


class Replica:
    """Mutable per-backend state (all writes under the controller's
    lock; reads from the proxy path are racy-by-design displays)."""

    __slots__ = ("url", "state", "reason", "fails", "inflight",
                 "trial_inflight", "queue_rows", "kv_in_use",
                 "kv_slots", "firing", "reachable", "ready",
                 "requests", "errors", "launched", "ckpt_wall",
                 "staleness", "p99_s")

    def __init__(self, url, launched=False):
        self.url = url
        self.state = ADMITTED
        self.reason = None
        self.fails = 0               # consecutive proxy failures
        self.inflight = 0
        self.trial_inflight = False  # the half-open probe slot
        self.queue_rows = 0.0
        self.kv_in_use = 0.0
        self.kv_slots = 0.0
        self.firing = []
        self.reachable = None
        self.ready = None
        self.requests = 0
        self.errors = 0
        self.launched = launched     # autoscaler-owned (stoppable)
        self.ckpt_wall = None        # None = pre-continual replica
        self.staleness = None
        self.p99_s = None            # None = p99 never scraped

    def describe(self):
        return {"url": self.url, "state": self.state,
                "reason": self.reason, "inflight": self.inflight,
                "queue_rows": self.queue_rows,
                "kv_in_use": self.kv_in_use,
                "kv_slots": self.kv_slots,
                "firing": list(self.firing),
                "consecutive_failures": self.fails,
                "requests_total": self.requests,
                "errors_total": self.errors,
                "launched": self.launched,
                "ckpt_wall": self.ckpt_wall,
                "staleness": self.staleness,
                "p99_s": self.p99_s}


class FleetController(Logger):
    """The control loop: scrape -> fleet snapshot -> eject/readmit
    decisions -> (optional) autoscaler evaluation -> cached status
    document. One daemon thread; ``tick(rows=...)`` is injectable for
    deterministic tests."""

    def __init__(self, targets, interval=1.0, scrape_timeout=2.0,
                 eject_failures=3, slo_eject=True, autoscaler=None,
                 full_scrape=False, refresher=None,
                 routing_policy="least-queue"):
        self.name = "router-fleet"
        self.interval = float(interval)
        self.scrape_timeout = float(scrape_timeout)
        if routing_policy not in ROUTING_POLICIES:
            raise ValueError("routing_policy %r not one of %s"
                             % (routing_policy,
                                ", ".join(ROUTING_POLICIES)))
        self.routing_policy = routing_policy
        self.eject_failures = int(eject_failures)
        self.slo_eject = bool(slo_eject)
        self.autoscaler = autoscaler
        self.refresher = refresher
        self.full_scrape = bool(full_scrape)
        self._lock = threading.Lock()
        self._replicas = {}          # url -> Replica (insert order)
        self._ring = HashRing()
        for url in targets:
            self._add_locked(_norm_url(url))
        self._thread = None
        self._stop = threading.Event()
        # long-lived scrape fan-out pool: one per controller, not one
        # per tick (thread churn on the hot control path)
        self._pool = ThreadPoolExecutor(
            max_workers=fleet.MAX_SCRAPE_WORKERS,
            thread_name_prefix="router-scrape")
        self.ticks = 0
        #: the cached /router/status document: rebuilt wholesale per
        #: tick, served with one attribute read (probe discipline)
        self.status_doc = self._build_status(
            [r.describe() for r in self._replicas.values()])
        self._publish_gauges()

    # -- membership ----------------------------------------------------

    def _add_locked(self, url, launched=False):
        if url not in self._replicas:
            self._replicas[url] = Replica(url, launched=launched)
            self._ring.add(url)

    def add_target(self, url, launched=False):
        url = _norm_url(url)
        with self._lock:
            self._add_locked(url, launched=launched)
        self.info("backend added: %s", url)

    def remove_target(self, url):
        url = _norm_url(url)
        with self._lock:
            if self._replicas.pop(url, None) is None:
                return False
            self._ring.remove(url)
        _G_INFLIGHT.get().labels(url).set(0)
        self.info("backend removed: %s", url)
        return True

    def targets(self):
        with self._lock:
            return list(self._replicas)

    def drain(self, url):
        """Stop routing NEW requests to ``url``; in-flight ones
        finish. -> remaining inflight count, or None if unknown."""
        url = _norm_url(url)
        with self._lock:
            r = self._replicas.get(url)
            if r is None:
                return None
            r.state = DRAINING
            r.reason = "draining"
            inflight = r.inflight
        telemetry.record_event("router_drain", replica=url,
                               inflight=inflight)
        self.info("draining %s (%d in flight)", url, inflight)
        return inflight

    def readmit(self, url):
        """Return a DRAINING replica to the routable set (the other
        half of :meth:`drain` — the rolling refresh re-admits each
        replica after its reload passes ``/readyz``). -> True when
        the state changed."""
        url = _norm_url(url)
        with self._lock:
            r = self._replicas.get(url)
            if r is None or r.state != DRAINING:
                return False
            r.state = ADMITTED
            r.reason = None
            r.fails = 0
        telemetry.record_event("router_readmit", replica=url)
        self.info("backend %s re-admitted after drain", url)
        return True

    def inflight(self, url):
        with self._lock:
            r = self._replicas.get(_norm_url(url))
            return None if r is None else r.inflight

    def counts(self):
        """(admitted, total) — what the router's readiness check and
        the backend gauges read."""
        with self._lock:
            total = len(self._replicas)
            admitted = sum(1 for r in self._replicas.values()
                           if r.state == ADMITTED)
        return admitted, total

    # -- lifecycle -----------------------------------------------------

    def ensure_started(self):
        with self._lock:
            if self._thread is None or not self._thread.is_alive():
                self._thread = threading.Thread(
                    target=self._loop, daemon=True,
                    name="router-fleet")
                self._thread.start()
        return self

    def _loop(self):
        while not self._stop.wait(self.interval):
            try:
                self.tick()
            except Exception as exc:   # the loop must outlive a bad
                self.warning("control tick failed: %s: %s",
                             type(exc).__name__, exc)

    def close(self):
        self._stop.set()
        thread = self._thread
        if thread is not None and thread.is_alive():
            thread.join(timeout=self.interval + 5.0)
        self._pool.shutdown(wait=False)

    # -- the tick ------------------------------------------------------

    def tick(self, rows=None):
        """One control evaluation. ``rows`` injects pre-scraped fleet
        rows (tests); otherwise every current target is scraped in
        parallel with the per-target budget."""
        urls = self.targets()
        if rows is None:
            rows = fleet.scrape_targets(
                urls, timeout=self.scrape_timeout,
                total=self.scrape_timeout,
                extras=self.full_scrape, pool=self._pool)
        by_url = {r.get("url"): r for r in rows if isinstance(r, dict)}
        with self._lock:
            for url, replica in self._replicas.items():
                row = by_url.get(url)
                if row is not None:
                    self._apply_row_locked(replica, row)
            self.ticks += 1
        if self.autoscaler is not None:
            try:
                self.autoscaler.evaluate(self)
            except Exception as exc:
                self.warning("autoscaler evaluation failed: %s: %s",
                             type(exc).__name__, exc)
        if self.refresher is not None:
            try:
                self.refresher.evaluate(self)
            except Exception as exc:
                self.warning("rolling-refresh evaluation failed: "
                             "%s: %s", type(exc).__name__, exc)
        with self._lock:
            self.status_doc = self._build_status(
                [r.describe() for r in self._replicas.values()])
        self._publish_gauges()
        return self.status_doc

    def _apply_row_locked(self, r, row):
        r.reachable = bool(row.get("reachable"))
        r.ready = row.get("ready")
        partial = bool(row.get("partial"))
        metrics = row.get("metrics") or {}
        if metrics or not partial:
            # a truncated scrape that never reached /metrics keeps
            # the PREVIOUS gauges: zeroing queue_rows would make the
            # slowest replica the least-queue routing magnet
            r.firing = list(row.get("firing") or ())
            r.queue_rows = float(
                metrics.get("serving_queue_rows") or 0.0)
            r.kv_in_use = float(
                metrics.get("kv_slots_in_use") or 0.0)
            r.kv_slots = float(metrics.get("kv_pool_slots") or 0.0)
            # absent on pre-continual replicas: keep None, never 0 —
            # the rolling refresh must not mistake "no gauge" for
            # "infinitely stale"
            wall = metrics.get("serving_ckpt_wall")
            r.ckpt_wall = float(wall) if wall else None
            stale = metrics.get("staleness_seconds")
            r.staleness = None if stale is None else float(stale)
            # absent on pre-18 replicas (or before any traffic):
            # keep None — the latency policy substitutes the fleet
            # median instead of treating "unknown" as "instant"
            p99 = metrics.get("serving_p99_s")
            r.p99_s = None if p99 is None else float(p99)
        if not r.reachable:
            reason, category = (
                "unreachable: %s" % row.get("error", "?"),
                "unreachable")
        elif r.ready is False:
            reason, category = (
                "not ready: %s" % "; ".join(
                    str(x) for x in row.get("reasons", ())),
                "not_ready")
        elif r.ready is None and partial:
            # the budget ran out before /readyz answered: a replica
            # too slow to scrape is too slow to route to — this IS
            # the 'scrape timeout ejects' policy (ready=None WITHOUT
            # partial is a pre-health-plane process and stays)
            reason, category = ("scrape truncated within budget",
                                "unreachable")
        elif self.slo_eject and r.firing:
            reason, category = (
                "slo firing: %s" % ", ".join(r.firing), "slo")
        else:
            reason = category = None
        if reason is not None:
            if r.state in (ADMITTED, HALF_OPEN):
                self._eject_locked(r, reason, category)
        elif r.state == EJECTED:
            # recovery seen by the scraper: half-open — the next
            # routed request is the probe (snapshot-store breaker
            # discipline: one trial, not a thundering readmit)
            r.state = HALF_OPEN
            r.reason = "half-open (probing after: %s)" % r.reason
            r.trial_inflight = False
            self.info("backend %s half-open after recovery", r.url)

    def _eject_locked(self, r, reason, category):
        r.state = EJECTED
        r.reason = reason
        r.trial_inflight = False
        _C_EJECT.get().labels(category).inc()
        telemetry.record_event("router_failover", replica=r.url,
                               reason=reason, category=category)
        self.warning("backend %s EJECTED: %s", r.url, reason)

    def _build_status(self, backends):
        doc = {"ts": round(time.time(), 3),
               "interval_s": self.interval,
               "ticks": self.ticks,
               "backends": backends,
               "admitted": sum(1 for b in backends
                               if b.get("state") == ADMITTED)}
        if self.autoscaler is not None:
            doc["autoscaler"] = self.autoscaler.describe()
        if self.refresher is not None:
            doc["rolling_refresh"] = self.refresher.describe()
        return doc

    def _publish_gauges(self):
        admitted, total = self.counts()
        g = _G_BACKENDS.get()
        g.labels("admitted").set(admitted)
        g.labels("total").set(total)

    # -- routing decisions (proxy path) --------------------------------

    def select(self, sticky_key=None, exclude=()):
        """Pick the backend for one request; -> Replica or None.

        A HALF-OPEN replica with a free trial slot wins first (the
        probe must happen for re-admission); then consistent-hash
        stickiness when the request carries a session key; then the
        configured load policy — least-queue (scraped queue depth +
        live inflight) or latency-aware (scraped serving p99
        weighted by that same load; see :data:`ROUTING_POLICIES`)."""
        with self._lock:
            candidates = [r for r in self._replicas.values()
                          if r.url not in exclude]
            for r in candidates:
                if r.state == HALF_OPEN and not r.trial_inflight:
                    r.trial_inflight = True
                    return r
            admitted = [r for r in candidates if r.state == ADMITTED]
            if not admitted:
                return None
            if sticky_key is not None:
                url = self._ring.lookup(
                    sticky_key, {r.url for r in admitted})
                if url is not None:
                    return self._replicas[url]
            if self.routing_policy == "latency":
                known = sorted(r.p99_s for r in admitted
                               if r.p99_s is not None)
                if known:
                    # expected wait ~ per-request p99 x (queued ahead
                    # + 1); unknown p99 (pre-18 replica, no traffic
                    # yet) prices at the fleet median — neither a
                    # magnet nor a pariah
                    med = known[len(known) // 2]
                    return min(
                        admitted,
                        key=lambda r: (
                            (r.p99_s if r.p99_s is not None else med)
                            * (1.0 + r.queue_rows
                               + 2.0 * r.inflight),
                            r.url))
            return min(admitted,
                       key=lambda r: (r.queue_rows + 2.0 * r.inflight,
                                      r.url))

    def has_alternative(self, exclude=()):
        """True while another ROUTABLE backend (admitted, or
        half-open with a free trial slot) remains outside
        ``exclude`` — what decides whether a shed/failed attempt may
        fail over instead of answering now."""
        with self._lock:
            return any(
                r.url not in exclude
                and (r.state == ADMITTED
                     or (r.state == HALF_OPEN
                         and not r.trial_inflight))
                for r in self._replicas.values())

    def begin(self, r):
        with self._lock:
            r.inflight += 1
            r.requests += 1
            inflight = r.inflight
        _G_INFLIGHT.get().labels(r.url).set(inflight)

    def finish(self, r):
        with self._lock:
            r.inflight = max(r.inflight - 1, 0)
            inflight = r.inflight
        _G_INFLIGHT.get().labels(r.url).set(inflight)

    def report_success(self, r):
        with self._lock:
            r.fails = 0
            r.trial_inflight = False
            readmitted = r.state == HALF_OPEN
            if readmitted:
                r.state = ADMITTED
                r.reason = None
        if readmitted:
            telemetry.record_event("router_readmit", replica=r.url)
            self.info("backend %s re-admitted (half-open probe ok)",
                      r.url)

    def report_failure(self, r, why):
        with self._lock:
            r.errors += 1
            r.fails += 1
            r.trial_inflight = False
            if r.state == HALF_OPEN:
                self._eject_locked(
                    r, "half-open probe failed: %s" % why, "errors")
            elif r.state == ADMITTED \
                    and r.fails >= self.eject_failures:
                self._eject_locked(
                    r, "%d consecutive proxy failures (last: %s)"
                    % (r.fails, why), "errors")


# -- autoscaling --------------------------------------------------------


class DryRunExecutor:
    """Decision-only executor (``--dry-run`` / no ``--scale-cmd``):
    scale events and counters fire, nothing is actuated."""

    actuates = False
    kind = "dry-run"

    def launch(self):
        return None

    def stop(self, url):
        pass

    def close(self):
        pass


class SubprocessExecutor(Logger):
    """Launches replica processes on THIS host (tests / single-host
    CPU fleets): ``argv_template`` entries are ``str.format``-ed with
    ``port`` (a freshly bound free port) and ``host``; launch blocks
    until the new replica answers ``/healthz`` or the timeout kills
    it."""

    actuates = True
    kind = "subprocess"

    def __init__(self, argv_template, host="127.0.0.1",
                 start_timeout=30.0, env=None):
        self.name = "router-exec"
        self.argv_template = list(argv_template)
        self.host = host
        self.start_timeout = float(start_timeout)
        #: extra environment entries merged over the parent's
        self.env = dict(env) if env else None
        self._procs = {}             # url -> Popen

    @staticmethod
    def _free_port(host):
        s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        try:
            s.bind((host, 0))
            return s.getsockname()[1]
        finally:
            s.close()

    def launch(self):
        port = self._free_port(self.host)
        argv = [a.format(port=port, host=self.host)
                for a in self.argv_template]
        url = "http://%s:%d" % (self.host, port)
        env = dict(os.environ, **self.env) if self.env else None
        proc = subprocess.Popen(argv, stdout=subprocess.DEVNULL,
                                stderr=subprocess.DEVNULL, env=env)
        # registered BEFORE the health poll: close() during an
        # in-flight launch must be able to reap this process instead
        # of orphaning it past the router's exit
        self._procs[url] = proc
        deadline = time.monotonic() + self.start_timeout
        while time.monotonic() < deadline:
            if proc.poll() is not None:
                self._procs.pop(url, None)
                self.warning("launched replica exited rc=%s before "
                             "becoming healthy: %s", proc.returncode,
                             " ".join(argv))
                return None
            try:
                with urllib.request.urlopen(url + "/healthz",
                                            timeout=1.0):
                    pass
                self.info("launched replica %s (pid %d)", url,
                          proc.pid)
                return url
            except Exception:
                time.sleep(0.2)
        self.stop(url)
        self.warning("launched replica never became healthy: %s",
                     " ".join(argv))
        return None

    def stop(self, url):
        proc = self._procs.pop(url, None)
        if proc is None:
            return False
        proc.terminate()
        try:
            proc.wait(timeout=10.0)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait(timeout=5.0)
        self.info("stopped replica %s", url)
        return True

    def close(self):
        for url in list(self._procs):
            self.stop(url)


class Autoscaler(Logger):
    """Burn rates and queue trajectories -> scale decisions.

    Evaluated once per control tick (on the controller thread):

    * **up** when any admitted backend's SLO burn-rate alert fires,
      or the mean scraped queue depth per admitted backend exceeds
      ``queue_high``, or NO backend is admitted at all — sustained
      for ``sustain_ticks`` ticks, subject to ``cooldown_s`` and
      ``max_replicas``;
    * **down** when the mean queue depth sits under ``queue_low``
      (and nothing fires) for ``sustain_ticks`` ticks above
      ``min_replicas`` — the victim (an executor-launched, least
      loaded replica) is DRAINED first and stopped only when its
      inflight reaches zero.

    Every decision is a ``scale_up``/``scale_down`` event and a
    ``veles_router_scale_decisions_total{direction}`` increment even
    under :class:`DryRunExecutor` — decision-only mode exists so the
    policy can be watched against a live fleet before it is trusted
    to actuate."""

    def __init__(self, executor, min_replicas=1, max_replicas=4,
                 queue_high=32.0, queue_low=2.0, sustain_ticks=3,
                 cooldown_s=30.0):
        self.name = "autoscaler"
        self.executor = executor
        self.min_replicas = int(min_replicas)
        self.max_replicas = int(max_replicas)
        self.queue_high = float(queue_high)
        self.queue_low = float(queue_low)
        self.sustain_ticks = int(sustain_ticks)
        self.cooldown_s = float(cooldown_s)
        self._high_ticks = 0
        self._low_ticks = 0
        self._last_action = None     # monotonic stamp of last actuation
        self._stopping = set()       # urls draining toward a stop
        self._launch_thread = None   # in-flight scale-up launch
        self.decisions = []          # newest-last, bounded

    def describe(self):
        return {"executor": self.executor.kind,
                "min": self.min_replicas, "max": self.max_replicas,
                "queue_high": self.queue_high,
                "queue_low": self.queue_low,
                "high_ticks": self._high_ticks,
                "low_ticks": self._low_ticks,
                "stopping": sorted(self._stopping),
                "last": self.decisions[-1] if self.decisions else None,
                "decisions": len(self.decisions)}

    def _record(self, direction, reason, url=None):
        decision = {"wall": round(time.time(), 3),
                    "direction": direction, "reason": reason,
                    "url": url, "executor": self.executor.kind,
                    "actuated": self.executor.actuates}
        self.decisions.append(decision)
        del self.decisions[:-64]
        _C_SCALE.get().labels(direction).inc()
        telemetry.record_event("scale_" + direction, reason=reason,
                               url=url or "-",
                               executor=self.executor.kind,
                               actuated=self.executor.actuates)
        self.info("scale_%s (%s): %s", direction, reason, url or "-")
        return decision

    def evaluate(self, controller):
        now = time.monotonic()
        # snapshot outside any controller lock: evaluate() runs on
        # the controller thread between locked phases
        with controller._lock:
            replicas = [(r.url, r.state, r.queue_rows,
                         list(r.firing), r.inflight, r.launched)
                        for r in controller._replicas.values()]
        self._finish_stops(controller, replicas)
        admitted = [r for r in replicas if r[1] == ADMITTED]
        total = len([r for r in replicas
                     if r[1] != DRAINING])    # draining is leaving
        # firing collected across EVERY non-draining backend: under
        # the default slo_eject a firing replica is ejected BEFORE
        # this runs, and the ejected one is exactly the capacity
        # signal scale-up must see
        firing = sorted({name for r in replicas
                         if r[1] != DRAINING for name in r[3]})
        mean_queue = (sum(r[2] for r in admitted) / len(admitted)) \
            if admitted else 0.0
        high = bool(firing) or not admitted \
            or mean_queue > self.queue_high
        low = not firing and admitted and mean_queue < self.queue_low
        self._high_ticks = self._high_ticks + 1 if high else 0
        self._low_ticks = self._low_ticks + 1 if low else 0
        in_cooldown = self._last_action is not None \
            and now - self._last_action < self.cooldown_s
        launching = self._launch_thread is not None \
            and self._launch_thread.is_alive()
        if self._high_ticks >= self.sustain_ticks \
                and total < self.max_replicas and not in_cooldown \
                and not launching:
            reason = "slo firing: %s" % ", ".join(firing) if firing \
                else ("no admitted backend" if not admitted
                      else "mean queue %.1f > %.1f"
                      % (mean_queue, self.queue_high))
            self._record("up", reason)
            self._high_ticks = 0
            self._last_action = now
            # launch OFF the control thread: a subprocess start polls
            # health for seconds, and a frozen control loop would
            # stall every ejection/re-admission meanwhile
            executor = self.executor

            def run_launch():
                url = executor.launch()
                if url is not None:
                    controller.add_target(url, launched=True)

            self._launch_thread = threading.Thread(
                target=run_launch, daemon=True,
                name="autoscaler-launch")
            self._launch_thread.start()
            return
        if self._low_ticks >= self.sustain_ticks \
                and len(admitted) > self.min_replicas \
                and not in_cooldown:
            victims = sorted(
                (r for r in admitted if r[5]),   # executor-launched
                key=lambda r: (r[4], r[2]))
            reason = "mean queue %.1f < %.1f" % (mean_queue,
                                                 self.queue_low)
            if not victims:
                if self.executor.actuates:
                    return           # nothing this executor may stop
                self._record("down", reason,
                             url=min(admitted)[0])
                self._low_ticks = 0
                self._last_action = now
                return
            url = victims[0][0]
            self._record("down", reason, url=url)
            self._low_ticks = 0
            self._last_action = now
            controller.drain(url)
            self._stopping.add(url)

    def _finish_stops(self, controller, replicas):
        """Stop drained victims whose inflight reached zero. The
        process stop itself runs OFF the control thread — a replica
        that ignores SIGTERM takes executor.stop() ~15s, and the
        loop's ejections/re-admissions must not freeze behind it
        (same discipline as the launch path)."""
        by_url = {r[0]: r for r in replicas}
        executor = self.executor
        for url in sorted(self._stopping):
            row = by_url.get(url)
            if row is None:
                self._stopping.discard(url)
                continue
            if row[4] == 0:          # inflight drained
                self._stopping.discard(url)
                controller.remove_target(url)

                def run_stop(url=url):
                    executor.stop(url)
                    telemetry.record_event("scale_down_complete",
                                           url=url)

                threading.Thread(target=run_stop, daemon=True,
                                 name="autoscaler-stop").start()

    def close(self):
        thread = self._launch_thread
        if thread is not None and thread.is_alive():
            # wait out an in-flight launch (its health poll runs up
            # to the executor's start_timeout) so executor.close()
            # sees — and reaps — the spawned process
            thread.join(timeout=getattr(
                self.executor, "start_timeout", 5.0) + 5.0)
        self.executor.close()


# -- rolling refresh (ISSUE 16) -----------------------------------------


class RollingRefresh(Logger):
    """Verified-checkpoint rolling fleet refresh: close the continual
    loop's last mile.

    Evaluated once per control tick (on the controller thread, same
    contract as :class:`Autoscaler`); every ``period_s`` it moves the
    whole roll OFF the control thread — a roll waits out drains and
    reload health polls for seconds, and ejections/re-admissions must
    not freeze behind it. The worker:

    1. scans the snapshot store newest-first, SKIPPING diverged
       verdicts (a poisoned update is never rolled out — the skip is
       logged with the blob name and recorded);
    2. picks the ADMITTED replicas whose scraped
       ``serving_ckpt_wall`` is older than the newest healthy
       checkpoint (replicas without the gauge — pre-continual
       processes — are left alone);
    3. rolls them STRICTLY one at a time: drain -> wait inflight 0 ->
       ``POST /v1/models/<m>/refresh`` -> wait ``/readyz`` -> readmit.

    A failed roll re-admits the replica anyway — serving the previous
    version beats serving nothing — and counts under
    ``veles_router_refreshes_total{outcome}``."""

    def __init__(self, store, model, period_s=30.0,
                 drain_timeout_s=30.0, ready_timeout_s=60.0,
                 http_timeout_s=5.0):
        self.name = "rolling-refresh"
        self.store = str(store)
        self.model = str(model)
        self.period_s = float(period_s)
        self.drain_timeout_s = float(drain_timeout_s)
        self.ready_timeout_s = float(ready_timeout_s)
        self.http_timeout_s = float(http_timeout_s)
        self._thread = None
        self._last_scan = None
        self._lock = threading.Lock()
        self.rolls = []              # newest-last, bounded
        self.newest = None           # newest healthy blob seen

    def describe(self):
        thread = self._thread
        with self._lock:
            return {"store": self.store, "model": self.model,
                    "period_s": self.period_s,
                    "rolling": bool(thread) and thread.is_alive(),
                    "newest_checkpoint": self.newest,
                    "last": self.rolls[-1] if self.rolls else None,
                    "rolls": len(self.rolls)}

    def evaluate(self, controller):
        now = time.monotonic()
        if self._thread is not None and self._thread.is_alive():
            return
        if self._last_scan is not None \
                and now - self._last_scan < self.period_s:
            return
        self._last_scan = now
        self._thread = threading.Thread(
            target=self._roll_fleet, args=(controller,), daemon=True,
            name="rolling-refresh")
        self._thread.start()

    def _newest_healthy(self):
        """Newest valid, NON-diverged checkpoint in the store (the
        scan already ranks corrupt/legacy blobs last)."""
        from veles import snapshotter
        try:
            infos = snapshotter.scan_checkpoints(self.store)
        except Exception as exc:
            self.warning("store scan of %s failed: %s: %s",
                         self.store, type(exc).__name__, exc)
            return None
        for info in infos:
            if info.status != "valid":
                continue
            if info.health_verdict == "diverged":
                telemetry.record_event("refresh_skipped_diverged",
                                       checkpoint=info.name,
                                       store=self.store)
                self.warning("rolling refresh SKIPPED diverged "
                             "checkpoint %s", info.name)
                continue
            return info
        return None

    def _roll_fleet(self, controller):
        info = self._newest_healthy()
        if info is None or info.wall_time is None:
            return
        with self._lock:
            self.newest = {"name": info.name,
                           "wall_time": info.wall_time}
        with controller._lock:
            stale = [r.url for r in controller._replicas.values()
                     if r.state == ADMITTED and r.ckpt_wall is not None
                     and float(info.wall_time) > r.ckpt_wall + 1e-6]
        for url in stale:            # strictly one at a time
            self._roll_one(controller, url, info)

    def _roll_one(self, controller, url, info):
        outcome, error = "ok", None
        t0 = time.monotonic()
        path = ("%s/%s" % (self.store.rstrip("/"), info.name)
                if self.store.startswith(("http://", "https://"))
                else os.path.join(self.store, info.name))
        try:
            if controller.drain(url) is None:
                outcome, error = "skipped", "replica left the fleet"
                return
            deadline = t0 + self.drain_timeout_s
            while (controller.inflight(url) or 0) > 0:
                if time.monotonic() >= deadline:
                    outcome, error = "failed", "drain timed out"
                    return
                time.sleep(0.05)
            body = json.dumps({"checkpoint": path,
                               "store": self.store}).encode()
            req = urllib.request.Request(
                "%s/v1/models/%s/refresh" % (url, self.model),
                data=body,
                headers={"Content-Type": "application/json"},
                method="POST")
            # the reload is synchronous on the replica side: the 200
            # means the new checkpoint serves
            with urllib.request.urlopen(
                    req, timeout=self.ready_timeout_s) as resp:
                json.load(resp)
            deadline = time.monotonic() + self.ready_timeout_s
            while True:
                try:
                    with urllib.request.urlopen(
                            url + "/readyz",
                            timeout=self.http_timeout_s) as resp:
                        if resp.status == 200:
                            break
                except OSError:      # 503 lands here too (HTTPError)
                    pass
                if time.monotonic() >= deadline:
                    outcome, error = \
                        "failed", "/readyz never recovered"
                    return
                time.sleep(0.1)
        except Exception as exc:
            outcome = "failed"
            error = "%s: %s" % (type(exc).__name__, exc)
        finally:
            # serving the previous version beats serving nothing: a
            # replica whose roll failed is re-admitted regardless
            controller.readmit(url)
            _C_REFRESH.get().labels(outcome).inc()
            telemetry.record_event("rolling_refresh", replica=url,
                                   checkpoint=info.name,
                                   outcome=outcome,
                                   error=error or "-")
            record = {"wall": round(time.time(), 3), "replica": url,
                      "checkpoint": info.name, "outcome": outcome,
                      "error": error,
                      "took_s": round(time.monotonic() - t0, 3)}
            with self._lock:
                self.rolls.append(record)
                del self.rolls[:-64]
            log = self.info if outcome == "ok" else self.warning
            log("rolled %s to %s: %s%s", url, info.name, outcome,
                "" if error is None else " (%s)" % error)

    def close(self):
        thread = self._thread
        if thread is not None and thread.is_alive():
            thread.join(timeout=self.ready_timeout_s + 5.0)


# -- the HTTP proxy -----------------------------------------------------


def _norm_url(url):
    url = str(url).rstrip("/")
    if "://" not in url:
        url = "http://" + url
    return url


def _host_port(url):
    # urlsplit, not string surgery: an IPv6 literal ([::1]:8080)
    # contains colons that a partition would misread as the port
    parts = urlsplit(url)
    return parts.hostname or "127.0.0.1", parts.port or 80


class RouterFrontend(Logger):
    """HTTP face of a :class:`FleetController`; port=0 picks a free
    one (see ``.port``). Proxied surfaces: everything under ``/v1/``.
    Own surfaces: probes, ``/metrics``(+``.json``), ``/debug/*``,
    ``/router/status``, ``POST /router/drain``."""

    def __init__(self, controller, port=0, host="127.0.0.1",
                 upstream_timeout=30.0):
        self.name = "router"
        self.controller = controller
        self.upstream_timeout = float(upstream_timeout)
        self._server = reactor.HttpServer(host, port, self._route,
                                          name="router-http",
                                          start=False)
        self.port = self._server.port
        self.host = host
        self.url = "http://%s:%d" % (host, self.port)
        self._check_names = ()
        self.register_health()
        controller.ensure_started()
        self._server.start()
        self.info("routing on http://%s:%d/ -> %s", host, self.port,
                  ", ".join(controller.targets()) or "(no backends)")

    # -- routing (reactor loop; inline routes must not block) ----------

    def _route(self, request):
        path = request.path
        if path.startswith("/v1/"):
            # every proxied request blocks on the upstream replica —
            # worker thread, replies posted back through the loop
            request.defer(self._proxy, request)
            return
        if path.startswith(("/healthz", "/readyz",
                            "/metrics/history")):
            # probe contract (zlint probe-purity): the monitor's
            # CACHED verdict, inline on the loop
            code, payload = health.health_endpoint(path)
            request.reply_json(code, payload)
        elif path.startswith("/router/status"):
            # the controller's cached per-tick document — one
            # attribute read, never a scrape
            request.reply_json(200, self.controller.status_doc)
        elif path.startswith("/router/drain"):
            if request.method != "POST":
                request.reply_json(404, {"error": "POST only"})
            else:
                request.defer(self._admin_drain, request)
        elif path.startswith("/metrics.json"):
            request.reply_json(200, self.metrics())
        elif path.startswith("/metrics"):
            reg = telemetry.get_registry()
            request.reply(200, reg.render_prometheus().encode(),
                          reg.CONTENT_TYPE)
        elif path.startswith("/debug/"):
            payload = telemetry.debug_endpoint(path)
            if payload is None:
                request.reply_json(404, {"error": "not found"})
            else:
                request.reply_json(200, payload)
        else:
            request.reply_json(404, {"error": "not found"})

    def metrics(self):
        return {"router": self.controller.status_doc}

    def _admin_drain(self, request):
        try:
            doc = json.loads(request.body)
            url = doc["url"]
        except (ValueError, KeyError, TypeError):
            request.reply_json(400, {"error": "body must be JSON "
                                              "with a 'url' key"})
            return
        inflight = self.controller.drain(url)
        if inflight is None:
            request.reply_json(404, {"error": "unknown backend %r"
                                     % url})
        else:
            request.reply_json(200, {"draining": _norm_url(url),
                                     "inflight": inflight})

    # -- readiness -----------------------------------------------------

    def register_health(self, monitor=None):
        monitor = monitor or health.get_monitor()
        self._monitor = monitor
        name = "router:%d:backends" % self.port
        self._check_names = (name,)
        monitor.add_check(name, self._check_backends)
        return monitor

    def _check_backends(self):
        """Ready iff at least one backend is routable — a router with
        an empty admitted set must tell its own upstream LB to stop
        sending (and an autoscaler to act)."""
        admitted, total = self.controller.counts()
        if admitted == 0:
            return False, ("0/%d backend(s) admitted" % total)
        return True, None

    # -- the proxy path (worker threads) -------------------------------

    def _sticky_key(self, request):
        """The consistent-hash key for a /v1/generate request, or
        None (-> least-queue). A session id makes a generation stream
        sticky to one replica's KV/prefix locality."""
        if not request.path.startswith("/v1/generate"):
            return None
        session = request.headers.get("x-veles-session")
        if session:
            return "session:%s" % session
        try:
            doc = json.loads(request.body)
            session = doc.get("session") if isinstance(doc, dict) \
                else None
        except ValueError:
            return None
        return "session:%s" % session if session else None

    def _proxy(self, request):
        t0 = time.perf_counter()
        trace = telemetry.TraceContext.from_traceparent(
            request.headers.get("traceparent"))
        if trace is None:
            trace = telemetry.TraceContext.new()
        tp_header = (("traceparent", trace.to_traceparent()),)
        with telemetry.context(trace):
            replica, code = self._proxy_attempts(request, trace,
                                                 tp_header)
        dt = time.perf_counter() - t0
        _H_LATENCY.get().observe(dt)
        if telemetry.tracer.active:
            args = {"code": code, "path": request.path,
                    "replica": replica.url if replica else "-"}
            args.update(trace.span_args())
            telemetry.tracer.add_complete("router.proxy", t0, dt,
                                          **args)

    def _proxy_attempts(self, request, trace, tp_header):
        """Route with failover: transport errors (and 503 sheds)
        before any downstream byte retry on the next-best backend;
        -> (replica|None, http_code) for the span."""
        controller = self.controller
        sticky = self._sticky_key(request)
        tenant = _resolve_tenant(request)
        tried = set()
        last_error = None
        for _ in range(max(len(controller.targets()), 1)):
            replica = controller.select(sticky_key=sticky,
                                        exclude=tried)
            if replica is None:
                break
            tried.add(replica.url)
            # only an actually-routable alternative justifies holding
            # back a replica's honest 503: with every other backend
            # ejected, THIS answer (Retry-After included) is the reply
            may_retry = controller.has_alternative(exclude=tried)
            controller.begin(replica)
            try:
                outcome, code, retry = self._forward(
                    request, replica, trace, tp_header, may_retry)
            except Exception as exc:
                # an unexpected fault (bad backend URL, bug) must
                # still settle the replica's trial slot and failure
                # accounting — a wedged HALF_OPEN probe slot would
                # otherwise starve the backend of traffic forever
                why = "%s: %s" % (type(exc).__name__, exc)
                controller.report_failure(replica, why)
                request.reply_json(502, {"error": why},
                                   headers=tp_header)
                outcome, code, retry = "error", 502, False
            finally:
                controller.finish(replica)
            _C_REQUESTS.get().labels(replica.url, tenant,
                                     outcome).inc()
            if not retry:
                return replica, code
            last_error = "%s -> %s" % (replica.url, outcome)
            telemetry.record_event("router_failover",
                                   replica=replica.url,
                                   reason="retrying after %s"
                                   % outcome, category="retry")
        reply = {"error": "no backend available",
                 "retry_after_s": RETRY_AFTER_NO_BACKEND}
        if last_error:
            reply["last_error"] = last_error
        _C_REQUESTS.get().labels("-", tenant, "no_backend").inc()
        request.reply_json(
            503, reply,
            headers=tp_header + (("Retry-After",
                                  str(RETRY_AFTER_NO_BACKEND)),))
        return None, 503

    def _forward(self, request, replica, trace, tp_header,
                 may_retry=False):
        """One upstream attempt; -> (outcome, code, retryable).
        While ``retryable`` is True NOTHING was written downstream —
        the caller may fail over to another backend."""
        hop = trace.child()
        host, port = _host_port(replica.url)
        headers = {"traceparent": hop.to_traceparent(),
                   "Connection": "close"}
        # x-veles-tenant rides the same hop as the traceparent: one
        # trace_id + tenant pair crosses client -> router -> replica
        for name in ("content-type", "accept", "x-veles-session",
                     "x-veles-tenant"):
            value = request.headers.get(name)
            if value:
                headers[name] = value
        addr = request.remote_addr
        if addr:
            # bare IP (XFF consumers parse comma-separated IPs, no
            # ports), APPENDED to an incoming chain so a router
            # behind another proxy preserves the original client
            client_ip = addr.rsplit(":", 1)[0]
            prior = request.headers.get("x-forwarded-for")
            headers["X-Forwarded-For"] = (
                "%s, %s" % (prior, client_ip) if prior else client_ip)
        conn = http.client.HTTPConnection(
            host, port, timeout=self.upstream_timeout)
        try:
            conn.request(request.method, request.path,
                         body=request.body or None, headers=headers)
            resp = conn.getresponse()
        except (OSError, http.client.HTTPException) as exc:
            conn.close()
            why = "%s: %s" % (type(exc).__name__, exc)
            self.controller.report_failure(replica, why)
            return "error", 502, True
        try:
            code = resp.status
            chunked = (resp.getheader("Transfer-Encoding") or "") \
                .lower() == "chunked"
            if code == 503 and not chunked:
                # replica-side shed/not-ready: an honest answer, not
                # a transport fault — another backend may have room,
                # so fail over while one remains untried; the LAST
                # backend's 503 (Retry-After included) passes through
                # verbatim
                body = resp.read()
                self.controller.report_success(replica)
                if may_retry:
                    return "shed", code, True
                retry_after = resp.getheader("Retry-After")
                extra = (("Retry-After", retry_after),) \
                    if retry_after else ()
                request.reply(
                    code, body,
                    resp.getheader("Content-Type") or "text/plain",
                    headers=tp_header + extra)
                return "shed", code, False
            if chunked:
                stream_ok = self._forward_stream(
                    request, replica, resp, tp_header, conn)
                return ("ok" if stream_ok else "error"), code, False
            body = resp.read()
            self.controller.report_success(replica)
            # a per-tenant 429 is the REPLICA's quota verdict: never
            # a failover (another backend shares the same table), and
            # its Retry-After — the bucket's exact refill time — must
            # reach the caller
            retry_after = resp.getheader("Retry-After")
            extra = (("Retry-After", retry_after),) \
                if retry_after else ()
            request.reply(
                code, body,
                resp.getheader("Content-Type") or "text/plain",
                headers=tp_header + extra)
            return ("ok" if code < 500 else "upstream_error"), \
                code, False
        except (OSError, http.client.HTTPException) as exc:
            why = "%s: %s" % (type(exc).__name__, exc)
            self.controller.report_failure(replica, why)
            # the response head was already consumed: not retryable
            request.reply_json(502, {"error": "upstream failed: %s"
                                     % why}, headers=tp_header)
            return "error", 502, False
        finally:
            conn.close()

    def _forward_stream(self, request, replica, resp, tp_header,
                        conn):
        """Relay a chunked upstream response (streaming decode)
        line-by-line through the reactor's bounded write queue; ->
        True unless the UPSTREAM failed mid-stream (counted as an
        error outcome). A downstream disconnect closes the upstream
        socket (the replica's own disconnect path then frees its KV
        slot) and still settles the replica's accounting as a
        success — the replica did nothing wrong, and a HALF-OPEN
        probe slot must never stay occupied past its request. An
        upstream stall/fault mid-stream becomes an error line, never
        a silent truncation."""
        gone = threading.Event()

        def on_close(_reason):
            # reactor loop: flag + socket close only, nothing blocking
            gone.set()
            try:
                sock = conn.sock
                if sock is not None:
                    sock.close()
            except OSError:
                pass

        stream = request.begin_stream(
            resp.status,
            resp.getheader("Content-Type") or "application/x-ndjson",
            headers=tp_header, on_close=on_close)
        ok = True
        try:
            while not gone.is_set():
                line = resp.readline()
                if not line:
                    break
                stream.write(line)
        except (OSError, http.client.HTTPException, ValueError) as exc:
            if not gone.is_set():
                ok = False
                self.controller.report_failure(
                    replica, "mid-stream: %s: %s"
                    % (type(exc).__name__, exc))
                stream.write(json.dumps(
                    {"error": "upstream failed mid-stream"}) + "\n")
        if ok:
            # normal end OR client disconnect: either way the
            # replica answered — settle its breaker/trial state
            self.controller.report_success(replica)
        stream.end()
        return ok

    def close(self):
        for name in self._check_names:
            self._monitor.remove_check(name, tick=False)
        if self._check_names:
            self._monitor.tick()
        self._check_names = ()
        self._server.close()


# -- velescli route -----------------------------------------------------


def build_route_argparser():
    p = argparse.ArgumentParser(
        prog="velescli route",
        description="Front N serving replicas behind one address: "
                    "least-queue/consistent-hash routing, eager "
                    "failover and autoscaling driven by the health "
                    "plane (veles/router.py)")
    p.add_argument("backends", nargs="+", metavar="URL",
                   help="serving replica base URLs "
                        "(http://host:port)")
    p.add_argument("--port", type=int, default=8080,
                   help="router HTTP port (0 = pick a free one)")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--interval", type=float, default=1.0,
                   help="control-loop tick period (seconds)")
    p.add_argument("--scrape-timeout", type=float, default=2.0,
                   help="per-backend scrape budget per tick — a "
                        "wedged replica is UNREACHABLE after this, "
                        "never a stall of the whole loop")
    p.add_argument("--eject-failures", type=int, default=3,
                   help="consecutive proxy failures that eject a "
                        "backend without waiting for the next scrape")
    p.add_argument("--no-slo-eject", action="store_true",
                   help="do not eject backends whose SLO burn-rate "
                        "alerts fire (readiness flips still eject)")
    p.add_argument("--upstream-timeout", type=float, default=30.0,
                   help="per-request upstream HTTP timeout")
    p.add_argument("--full-scrape", action="store_true",
                   help="scrape the heavyweight surfaces too "
                        "(status.json, critical path) each tick")
    p.add_argument("--autoscale", default=None, metavar="MIN:MAX",
                   help="enable the autoscaler with this replica "
                        "range (e.g. 1:4)")
    p.add_argument("--scale-cmd", default=None, metavar="CMD",
                   help="replica launch command template for scale-"
                        "up (shlex-split; '{port}'/'{host}' are "
                        "substituted, e.g. \"python -m veles serve "
                        "--model m=/dir --port {port}\"). Without "
                        "it (or with --dry-run) decisions are "
                        "recorded but not actuated")
    p.add_argument("--dry-run", action="store_true",
                   help="autoscaler records decisions only")
    p.add_argument("--queue-high", type=float, default=32.0,
                   help="mean queue rows per admitted backend that "
                        "reads as overload")
    p.add_argument("--queue-low", type=float, default=2.0,
                   help="mean queue rows under which scale-down is "
                        "considered")
    p.add_argument("--sustain-ticks", type=int, default=3,
                   help="control ticks a signal must persist before "
                        "the autoscaler acts")
    p.add_argument("--cooldown", type=float, default=30.0,
                   help="seconds between autoscaler actions")
    p.add_argument("--refresh-store", default=None, metavar="TARGET",
                   help="snapshot store (dir or http base) to watch "
                        "for newer HEALTHY checkpoints; with "
                        "--refresh-model, enables the rolling fleet "
                        "refresh (diverged blobs never roll out)")
    p.add_argument("--refresh-model", default=None, metavar="NAME",
                   help="served model name the rolling refresh "
                        "reloads on each replica")
    p.add_argument("--refresh-period", type=float, default=30.0,
                   metavar="SECS",
                   help="seconds between rolling-refresh store scans")
    p.add_argument("--slo-config", default=None, metavar="PATH",
                   help="JSON list of SLO objectives for the "
                        "router's own health monitor (e.g. on "
                        "veles_router_request_seconds:p99)")
    p.add_argument("--routing-policy", default="least-queue",
                   choices=ROUTING_POLICIES,
                   help="backend selection: least-queue (default) "
                        "or latency — scraped serving p99 weighted "
                        "by live load (backends without a p99 price "
                        "at the fleet median)")
    p.add_argument("--tenants", default=None, metavar="PATH",
                   help="tenant config (same JSON as serve "
                        "--tenants): bounds the router's per-tenant "
                        "request labels; the raw x-veles-tenant "
                        "header is forwarded to the replica either "
                        "way")
    return p


def route_main(argv=None):
    """``velescli route URL [URL...]`` — run the router until
    interrupted (SIGINT or SIGTERM; both run the cleanup that reaps
    autoscaler-launched replicas)."""
    args = build_route_argparser().parse_args(argv)
    telemetry.tracer.set_process_name("router")
    autoscaler = None
    if args.autoscale:
        try:
            lo, _, hi = args.autoscale.partition(":")
            lo, hi = int(lo), int(hi)
        except ValueError:
            raise SystemExit("--autoscale wants MIN:MAX, got %r"
                             % args.autoscale)
        if args.scale_cmd and not args.dry_run:
            executor = SubprocessExecutor(
                shlex.split(args.scale_cmd), host=args.host)
        else:
            executor = DryRunExecutor()
        autoscaler = Autoscaler(
            executor, min_replicas=lo, max_replicas=hi,
            queue_high=args.queue_high, queue_low=args.queue_low,
            sustain_ticks=args.sustain_ticks,
            cooldown_s=args.cooldown)
    refresher = None
    if args.refresh_store or args.refresh_model:
        if not (args.refresh_store and args.refresh_model):
            raise SystemExit("--refresh-store and --refresh-model "
                             "go together")
        refresher = RollingRefresh(args.refresh_store,
                                   args.refresh_model,
                                   period_s=args.refresh_period)
    if args.tenants:
        tenants.set_table(tenants.TenantTable.from_file(args.tenants))
    controller = FleetController(
        args.backends, interval=args.interval,
        scrape_timeout=args.scrape_timeout,
        eject_failures=args.eject_failures,
        slo_eject=not args.no_slo_eject, autoscaler=autoscaler,
        full_scrape=args.full_scrape, refresher=refresher,
        routing_policy=args.routing_policy)
    front = None
    try:
        front = RouterFrontend(controller, port=args.port,
                               host=args.host,
                               upstream_timeout=args.upstream_timeout)
        if args.slo_config:
            n = health.get_monitor().load_slo_file(args.slo_config)
            front.info("%d SLO objective(s) loaded from %s", n,
                       args.slo_config)
        print(json.dumps({
            "router": front.url,
            "backends": controller.targets(),
            "autoscale": args.autoscale,
        }), flush=True)
        # SIGTERM must run the finally below (reap launched
        # replicas, close the server)
        from veles.serving.frontend import wait_for_interrupt
        wait_for_interrupt()
    finally:
        if front is not None:
            front.close()
        controller.close()
        if autoscaler is not None:
            autoscaler.close()
        if refresher is not None:
            refresher.close()
    return 0


if __name__ == "__main__":
    sys.exit(route_main())
