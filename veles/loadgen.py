"""``velescli loadgen`` — open-loop load generation with tenant
mixes (ISSUE 18).

The QoS layer's proof harness: a Poisson-arrival (open-loop)
generator drives mixed predict/generate traffic at a routed fleet or
a single replica, per arrival picking a tenant from the configured
mix and stamping its ``x-veles-tenant`` header, and reports
goodput/p99/shed-rate CURVES per tenant across an arrival-rate ramp.

Open loop matters: a closed-loop client (send, wait, send) slows
down exactly when the service does, flattering p99 at the point of
saturation — the "coordinated omission" trap. Here arrivals are
scheduled by the clock (exponential inter-arrival gaps, never waiting
on completions), so offered load keeps arriving while the fleet
chokes and the shed/latency curves show the choke honestly.

The summary row is the capacity number ROADMAP item 4 asks for::

    {"metric": "routed_capacity_rps_at_p99_slo", "value": R, ...}

— the highest offered rps stage at which the FIRST configured tenant
(the "compliant" one by convention) kept its p99 inside
``--p99-slo-ms`` with a shed rate under ``--max-shed``.
"""

import argparse
import json
import random
import sys
import threading
import time
import urllib.error
import urllib.request
from concurrent.futures import ThreadPoolExecutor

#: dispatch pool width: enough in-flight sockets that the generator
#: never blocks on completions at test-scale rates (true open loop up
#: to ~hundreds of concurrently outstanding requests)
MAX_WORKERS = 64


class _TenantMix:
    """Weighted tenant shares; ``pick(rng)`` draws one arrival."""

    def __init__(self, shares):
        # [(name, share)] normalized; order preserved (first tenant
        # is the capacity row's compliant subject)
        total = sum(s for _, s in shares)
        self.names = [name for name, _ in shares]
        self._cum = []
        acc = 0.0
        for name, share in shares:
            acc += share / total
            self._cum.append((acc, name))

    def pick(self, rng):
        x = rng.random()
        for edge, name in self._cum:
            if x <= edge:
                return name
        return self._cum[-1][1]


def _parse_tenants(specs):
    """--tenant NAME[:SHARE] (repeatable) -> [(name, share)]."""
    out = []
    for spec in specs or ["anon"]:
        name, sep, share = spec.partition(":")
        if not name:
            raise SystemExit("--tenant %r: expected NAME[:SHARE]"
                             % spec)
        try:
            out.append((name, float(share) if sep else 1.0))
        except ValueError:
            raise SystemExit("--tenant %r: bad share" % spec)
    return out


def _fetch_json(url, timeout=10.0):
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return json.loads(resp.read())


def _percentile(sorted_vals, q):
    if not sorted_vals:
        return None
    idx = max(0, min(len(sorted_vals) - 1,
                     int(q * len(sorted_vals) + 0.5) - 1))
    return sorted_vals[idx]


class _Stats:
    """One (stage, tenant) bucket; thread-safe counters + latency."""

    def __init__(self):
        self.lock = threading.Lock()
        self.offered = 0
        self.ok = 0
        self.shed = 0                # 429 quota + 503 shed/not-ready
        self.errors = 0
        self.latencies = []          # seconds, answered requests only

    def record(self, code, dt):
        with self.lock:
            if code is not None and 200 <= code < 300:
                self.ok += 1
                self.latencies.append(dt)
            elif code in (429, 503):
                self.shed += 1
            else:
                self.errors += 1

    def summary(self, duration):
        lat = sorted(self.latencies)
        p50 = _percentile(lat, 0.50)
        p99 = _percentile(lat, 0.99)
        return {
            "offered": self.offered, "ok": self.ok,
            "shed": self.shed, "errors": self.errors,
            "goodput_rps": round(self.ok / duration, 2),
            "shed_rate": round(self.shed / max(self.offered, 1), 4),
            "p50_ms": None if p50 is None else round(p50 * 1e3, 2),
            "p99_ms": None if p99 is None else round(p99 * 1e3, 2),
        }


def _one_request(url, body, tenant, timeout, stats):
    t0 = time.perf_counter()
    req = urllib.request.Request(
        url, data=body,
        headers={"Content-Type": "application/json",
                 "x-veles-tenant": tenant})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            resp.read()
            code = resp.status
    except urllib.error.HTTPError as exc:
        exc.read()
        code = exc.code
    except Exception:
        code = None
    stats.record(code, time.perf_counter() - t0)


#: ceiling on the zero-sample the target's advertised geometry may
#: make us build — the /v1/models listing is the TARGET's data, and a
#: malicious or buggy target advertising [1 << 30] must not OOM the
#: load generator (zlint untrusted-geometry)
_MAX_SAMPLE_ELEMENTS = 1 << 20
_MAX_SAMPLE_RANK = 8


def _validated_shape(shape):
    """Bound target-advertised ``input_sample_shape`` before any
    allocation keys off it; -> a list of positive ints, or
    SystemExit naming the refused geometry."""
    dims = []
    total = 1
    for dim in list(shape)[:_MAX_SAMPLE_RANK]:
        try:
            dim = int(dim)
        except (TypeError, ValueError):
            raise SystemExit(
                "target advertises a non-numeric input_sample_shape "
                "entry %r" % (dim,))
        dims.append(max(dim, 1))
        total *= max(dim, 1)
    if len(list(shape)) > _MAX_SAMPLE_RANK \
            or total > _MAX_SAMPLE_ELEMENTS:
        raise SystemExit(
            "target advertises input_sample_shape %r (%d elements) — "
            "refusing to build a sample beyond %d elements"
            % (list(shape), total, _MAX_SAMPLE_ELEMENTS))
    return dims or [1]


def _predict_body(base, model_arg, timeout=10.0):
    """(model name, canned /v1/predict body, generative?) derived
    from the target's ``/v1/models`` listing — a zero-valued sample
    of the model's recorded input shape prices the real forward."""
    doc = _fetch_json(base + "/v1/models", timeout=timeout)
    models = doc.get("models") or []
    if not models:
        raise SystemExit("target serves no models")
    if model_arg:
        matches = [m for m in models if m.get("name") == model_arg]
        if not matches:
            raise SystemExit("target does not serve model %r "
                             "(has: %s)" % (model_arg, ", ".join(
                                 sorted(m.get("name", "?")
                                        for m in models))))
        m = matches[0]
    else:
        m = models[0]
    name = m["name"]
    shape = _validated_shape(m.get("input_sample_shape") or [1])

    def zeros(dims):
        if not dims:
            return 0.0
        return [zeros(dims[1:]) for _ in range(int(dims[0]))]

    body = json.dumps({"model": name,
                       "inputs": [zeros(shape)]}).encode()
    return name, body, bool(m.get("generative"))


def run_stage(base, rate, duration, mix, bodies, rng, pool,
              timeout_s, generate_ratio):
    """One open-loop stage at ``rate`` rps for ``duration`` seconds;
    -> {tenant: _Stats}. Arrivals are clock-scheduled; dispatch rides
    the pool so a slow reply NEVER delays the next arrival."""
    stats = {name: _Stats() for name in mix.names}
    predict_url, predict_body, generate_body = bodies
    futures = []
    t_next = time.monotonic()
    t_end = t_next + duration
    while t_next < t_end:
        now = time.monotonic()
        if t_next > now:
            time.sleep(t_next - now)
        tenant = mix.pick(rng)
        s = stats[tenant]
        s.offered += 1
        if generate_body is not None \
                and rng.random() < generate_ratio:
            url, body = base + "/v1/generate", generate_body
        else:
            url, body = predict_url, predict_body
        futures.append(pool.submit(
            _one_request, url, body, tenant, timeout_s, s))
        t_next += rng.expovariate(rate)
    # drain between stages: each stage's curve must price ITS offered
    # load, not inherit the previous stage's stragglers
    for f in futures:
        f.result()
    return stats


def build_loadgen_argparser():
    p = argparse.ArgumentParser(
        prog="velescli loadgen",
        description="Open-loop (Poisson-arrival) load generator "
                    "with tenant mixes and arrival-rate ramps; "
                    "reports per-tenant goodput/p99/shed curves and "
                    "the routed_capacity_rps_at_p99_slo bench row")
    p.add_argument("target", metavar="URL",
                   help="router or serving base URL "
                        "(http://host:port)")
    p.add_argument("--tenant", action="append", default=[],
                   metavar="NAME[:SHARE]",
                   help="tenant mix entry (repeatable; shares "
                        "normalize; default one 'anon' tenant). The "
                        "FIRST tenant is the compliant subject of "
                        "the capacity row")
    p.add_argument("--rps", action="append", type=float, default=[],
                   metavar="RATE",
                   help="offered arrival rate per ramp stage "
                        "(repeatable, e.g. --rps 20 --rps 50 "
                        "--rps 100; default 20)")
    p.add_argument("--duration", type=float, default=5.0,
                   metavar="SECS", help="seconds per ramp stage")
    p.add_argument("--model", default=None,
                   help="served model to drive (default: the "
                        "target's first)")
    p.add_argument("--generate-ratio", type=float, default=0.0,
                   metavar="FRAC",
                   help="fraction of arrivals sent to /v1/generate "
                        "(needs a generative model; non-streaming)")
    p.add_argument("--max-tokens", type=int, default=8,
                   help="decode budget per generate arrival")
    p.add_argument("--p99-slo-ms", type=float, default=250.0,
                   help="the compliant tenant's p99 objective the "
                        "capacity row is judged against")
    p.add_argument("--max-shed", type=float, default=0.01,
                   metavar="FRAC",
                   help="max compliant-tenant shed rate for a stage "
                        "to count as within capacity")
    p.add_argument("--timeout-ms", type=float, default=10000.0,
                   help="per-request client timeout")
    p.add_argument("--seed", type=int, default=1234,
                   help="arrival/tenant-pick RNG seed")
    p.add_argument("--json", action="store_true",
                   help="print ONE machine-readable report (the "
                        "bench row with per-stage curves in 'extra') "
                        "instead of the table")
    return p


def loadgen_main(argv=None):
    args = build_loadgen_argparser().parse_args(argv)
    base = args.target.rstrip("/")
    if "://" not in base:
        base = "http://" + base
    rates = args.rps or [20.0]
    mix = _TenantMix(_parse_tenants(args.tenant))
    compliant = mix.names[0]
    rng = random.Random(args.seed)
    model, predict_body, generative = _predict_body(base, args.model)
    generate_body = None
    if args.generate_ratio > 0:
        if not generative:
            raise SystemExit("--generate-ratio: model %r is not "
                             "generative" % model)
        generate_body = json.dumps({
            "model": model, "prompt": [1, 2, 3],
            "max_tokens": args.max_tokens,
            "stream": False}).encode()
    bodies = (base + "/v1/predict", predict_body, generate_body)
    stages = []
    capacity = 0.0
    with ThreadPoolExecutor(max_workers=MAX_WORKERS,
                            thread_name_prefix="loadgen") as pool:
        for rate in rates:
            stats = run_stage(
                base, rate, args.duration, mix, bodies, rng, pool,
                args.timeout_ms / 1000.0, args.generate_ratio)
            per_tenant = {name: s.summary(args.duration)
                          for name, s in stats.items()}
            stages.append({"offered_rps": rate,
                           "duration_s": args.duration,
                           "tenants": per_tenant})
            c = per_tenant[compliant]
            if c["p99_ms"] is not None \
                    and c["p99_ms"] <= args.p99_slo_ms \
                    and c["shed_rate"] <= args.max_shed:
                capacity = max(capacity, rate)
    report = {
        "metric": "routed_capacity_rps_at_p99_slo",
        "value": capacity,
        "extra": {
            "target": base, "model": model,
            "compliant_tenant": compliant,
            "p99_slo_ms": args.p99_slo_ms,
            "max_shed": args.max_shed,
            "generate_ratio": args.generate_ratio,
            "stages": stages,
        },
    }
    if args.json:
        print(json.dumps(report, indent=2))
        return 0
    print("loadgen %s model=%s mix=%s" % (base, model,
                                          ",".join(mix.names)))
    print("%-8s %-10s %8s %8s %8s %9s %9s"
          % ("rps", "tenant", "ok", "shed", "errors",
             "p99_ms", "goodput"))
    for stage in stages:
        for name in mix.names:
            s = stage["tenants"][name]
            print("%-8g %-10s %8d %8d %8d %9s %9s"
                  % (stage["offered_rps"], name, s["ok"], s["shed"],
                     s["errors"],
                     "-" if s["p99_ms"] is None else s["p99_ms"],
                     s["goodput_rps"]))
    print("routed_capacity_rps_at_p99_slo %g  (tenant %s, "
          "p99 <= %gms, shed <= %g%%)"
          % (capacity, compliant, args.p99_slo_ms,
             args.max_shed * 100.0))
    return 0


if __name__ == "__main__":
    sys.exit(loadgen_main())
