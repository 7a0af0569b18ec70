"""zlint (veles/analysis/) — rule-by-rule fixtures + the repo gate.

Each rule gets a minimal violating snippet that must FIRE and a
corrected (or pragma'd) version that must stay QUIET; the CLI contract
(exit codes, sorted JSON shape) is pinned; and the tier-1 gate at the
bottom runs the full analyzer over the installed ``veles`` package and
asserts zero findings — every rule violation introduced anywhere in
the tree from now on fails CI until fixed or pragma'd with a reason.
No device needed: everything here is pure AST work.
"""

import json
import os

import pytest

from veles.analysis import analyze_paths
from veles.analysis.cli import lint_main


def lint_src(tmp_path, source, relname="mod.py", select=None):
    """Write ``source`` at ``relname`` under tmp and analyze it."""
    path = tmp_path / relname
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(source)
    return analyze_paths([str(path)], base=str(tmp_path),
                         select=select)


def rule_ids(findings):
    return [f.rule for f in findings]


# -- tracer-purity -----------------------------------------------------

_PURITY_BAD = """\
import numpy
import time


class Op:
    def xla_run(self, ctx):
        x = ctx.get("x")
        numpy.random.rand(3)
        time.time()
        print("traced")
        bad = x.sum().item()
        worse = float(x)
        self.cache = bad + worse
        return self.helper(ctx)

    def helper(self, ctx):
        self.hidden = 1
"""

_PURITY_GOOD = """\
import numpy


def _shape_prod(shape):
    return int(numpy.prod(shape))


class Op:
    def xla_run(self, ctx):
        x = ctx.get("x")
        n = _shape_prod((3, 4))
        return x.sum() / n
"""


def test_tracer_purity_fires_on_all_impurities(tmp_path):
    findings = lint_src(tmp_path, _PURITY_BAD,
                        relname="znicz_tpu/ops/fake.py",
                        select=["tracer-purity"])
    msgs = "\n".join(f.message for f in findings)
    assert "numpy.random.rand" in msgs
    assert "time.time" in msgs
    assert "print()" in msgs
    assert ".item()" in msgs
    assert "float()" in msgs
    assert "mutates self.cache" in msgs
    # the self.helper() call is followed: its mutation is caught too
    assert "mutates self.hidden" in msgs


def test_tracer_purity_quiet_on_pure_op_and_outside_ops(tmp_path):
    assert lint_src(tmp_path, _PURITY_GOOD,
                    relname="znicz_tpu/ops/fake.py",
                    select=["tracer-purity"]) == []
    # same impure source OUTSIDE znicz_tpu/ops is not traced code
    assert lint_src(tmp_path, _PURITY_BAD, relname="host_unit.py",
                    select=["tracer-purity"]) == []


def test_tracer_purity_catches_every_import_spelling(tmp_path):
    # the bans must not be dodgeable by import style
    src = """\
from numpy import random
from time import monotonic
import numpy.random
import time as clock


class Op:
    def xla_run(self, ctx):
        random.rand(3)
        monotonic()
        numpy.random.standard_normal(2)
        clock.sleep(0.1)
        return ctx.get("x")
"""
    findings = lint_src(tmp_path, src,
                        relname="znicz_tpu/ops/fake.py",
                        select=["tracer-purity"])
    msgs = "\n".join(f.message for f in findings)
    assert "random.rand" in msgs
    assert "monotonic" in msgs
    assert "numpy.random.standard_normal" in msgs
    assert "clock.sleep" in msgs
    assert len(findings) == 4


def test_tracer_purity_follows_module_alias_helpers(tmp_path):
    # `H.noisy(x)` — the dominant helper-call style in ops/ — must be
    # followed into the helper module
    helpers = """\
import numpy


def noisy(x):
    return x + numpy.random.uniform()
"""
    op = """\
from znicz_tpu.ops import helpers as H
from znicz_tpu.ops.helpers import noisy


class Op:
    def xla_run(self, ctx):
        a = H.noisy(ctx.get("x"))
        return noisy(a)
"""
    (tmp_path / "znicz_tpu" / "ops").mkdir(parents=True)
    (tmp_path / "znicz_tpu" / "ops" / "helpers.py").write_text(helpers)
    (tmp_path / "znicz_tpu" / "ops" / "op.py").write_text(op)
    findings = analyze_paths([str(tmp_path)], base=str(tmp_path),
                             select=["tracer-purity"])
    assert len(findings) == 1          # shared helper reported ONCE
    assert findings[0].rule == "tracer-purity"
    assert "numpy.random.uniform" in findings[0].message
    assert findings[0].file.endswith("helpers.py")


def test_tracer_purity_taint_propagates_through_locals(tmp_path):
    # float(s) where s DERIVES from a ctx read concretizes a tracer
    # just as surely as float(ctx.get(...)) does
    src = """\
class Op:
    def xla_run(self, ctx):
        t = ctx.get("x")
        s = t * 2
        k = float(s)
        return k
"""
    findings = lint_src(tmp_path, src,
                        relname="znicz_tpu/ops/fake.py",
                        select=["tracer-purity"])
    assert rule_ids(findings) == ["tracer-purity"]
    assert "float()" in findings[0].message


def test_tracer_purity_int_on_static_shapes_is_legitimate(tmp_path):
    src = """\
import numpy


class Op:
    def xla_run(self, ctx):
        n = int(numpy.prod((2, 3)))
        return n
"""
    assert lint_src(tmp_path, src, relname="znicz_tpu/ops/fake.py",
                    select=["tracer-purity"]) == []


# -- lock-order --------------------------------------------------------

_LOCK_CYCLE = """\
import threading


class A:
    def __init__(self):
        self._a = threading.Lock()
        self._b = threading.Lock()

    def m1(self):
        with self._a:
            with self._b:
                pass

    def m2(self):
        with self._b:
            with self._a:
                pass
"""

_LOCK_ORDERED = """\
import threading


class A:
    def __init__(self):
        self._a = threading.Lock()
        self._b = threading.Lock()

    def m1(self):
        with self._a:
            with self._b:
                pass

    def m2(self):
        with self._a:
            with self._b:
                pass
"""


def test_lock_order_cycle_fires(tmp_path):
    findings = lint_src(tmp_path, _LOCK_CYCLE,
                        select=["lock-order"])
    assert rule_ids(findings) == ["lock-order"]
    assert "cycle" in findings[0].message


def test_lock_order_quiet_on_consistent_order(tmp_path):
    assert lint_src(tmp_path, _LOCK_ORDERED,
                    select=["lock-order"]) == []


def test_lock_order_interprocedural_reentry(tmp_path):
    # the deadlock spans two methods: r1 holds the non-reentrant lock
    # and CALLS r2, which takes it again
    src = """\
import threading


class A:
    def __init__(self):
        self._a = threading.Lock()

    def r1(self):
        with self._a:
            self.r2()

    def r2(self):
        with self._a:
            pass
"""
    findings = lint_src(tmp_path, src, select=["lock-order"])
    assert rule_ids(findings) == ["lock-order"]
    assert "re-acquired" in findings[0].message
    # an RLock makes the same shape legal
    assert lint_src(tmp_path, src.replace("threading.Lock",
                                          "threading.RLock"),
                    select=["lock-order"]) == []


def test_lock_order_sees_inside_except_handlers(tmp_path):
    # retry/error paths are exactly where this codebase takes locks;
    # handler bodies must not be a blind spot
    src = """\
import threading


class A:
    def __init__(self):
        self._a = threading.Lock()
        self._b = threading.Lock()

    def m1(self):
        try:
            pass
        except Exception:
            with self._a:
                with self._b:
                    pass

    def m2(self):
        with self._b:
            with self._a:
                pass
"""
    findings = lint_src(tmp_path, src, select=["lock-order"])
    assert rule_ids(findings) == ["lock-order"]
    assert "cycle" in findings[0].message


def test_lock_order_multi_item_with_statement(tmp_path):
    # `with self.a, self.b:` orders a before b exactly like nesting
    src = """\
import threading


class A:
    def __init__(self):
        self.a = threading.Lock()
        self.b = threading.Lock()

    def m1(self):
        with self.a, self.b:
            pass

    def m2(self):
        with self.b:
            with self.a:
                pass
"""
    findings = lint_src(tmp_path, src, select=["lock-order"])
    assert rule_ids(findings) == ["lock-order"]
    assert "cycle" in findings[0].message
    # `with self.a, self.a:` deadlocks immediately on a Lock
    dup = """\
import threading


class A:
    def __init__(self):
        self.a = threading.Lock()

    def m1(self):
        with self.a, self.a:
            pass
"""
    findings = lint_src(tmp_path, dup, select=["lock-order"])
    assert rule_ids(findings) == ["lock-order"]
    assert "re-acquired" in findings[0].message


def test_lock_order_follows_inherited_attr_binding(tmp_path):
    # self.store is bound by the BASE __init__; the subclass's
    # `with self._big: self.store.put()` must still record the
    # _big -> Store._lock ordering edge (white-box: edges feed the
    # cycle detector, and a dropped edge = an invisible deadlock)
    src = """\
import threading


class Store:
    def __init__(self):
        self._lock = threading.Lock()

    def put(self):
        with self._lock:
            pass


class Base:
    def __init__(self):
        self._big = threading.Lock()
        self.store = Store()


class Child(Base):
    def f(self):
        with self._big:
            self.store.put()
"""
    from veles.analysis.core import build_project
    from veles.analysis.rules_threads import _LockWalker
    path = tmp_path / "m.py"
    path.write_text(src)
    proj = build_project([str(path)], base=str(tmp_path))
    walker = _LockWalker(proj)
    mod = proj.modules[0]
    for cls in mod.classes.values():
        for mname, meth in cls.methods.items():
            walker.walk_function(mod, cls, meth, [],
                                 ["%s.%s" % (cls.name, mname)])
    assert (("Base", "_big"), ("Store", "_lock")) in walker.edges


def test_lock_order_resolves_inherited_locks(tmp_path):
    # a subclass re-acquiring the non-reentrant lock its BASE bound
    # in __init__ is a guaranteed runtime deadlock; per-class-only
    # lookup used to lint it clean
    src = """\
import threading


class Base:
    def __init__(self):
        self._lock = threading.Lock()


class Child(Base):
    def work(self):
        with self._lock:
            self.helper()

    def helper(self):
        with self._lock:
            pass
"""
    findings = lint_src(tmp_path, src, select=["lock-order"])
    assert rule_ids(findings) == ["lock-order"]
    assert "re-acquired" in findings[0].message
    # the graph node is keyed by the DEFINING class
    assert "Base._lock" in findings[0].message


# -- unguarded-shared-state --------------------------------------------

_RACE = """\
import threading


class W:
    def __init__(self):
        self._lock = threading.Lock()
        threading.Thread(target=self._work, daemon=True).start()

    def _work(self):
        self.value = 1

    def set_value(self, v):
        self.value = v
"""


def test_unguarded_shared_state_fires(tmp_path):
    findings = lint_src(tmp_path, _RACE,
                        select=["unguarded-shared-state"])
    assert rule_ids(findings) == ["unguarded-shared-state"]
    assert "W.value" in findings[0].message


def test_unguarded_shared_state_sees_except_handler_writes(tmp_path):
    src = _RACE.replace(
        "        self.value = 1",
        "        try:\n"
        "            pass\n"
        "        except Exception:\n"
        "            self.value = 1")
    findings = lint_src(tmp_path, src,
                        select=["unguarded-shared-state"])
    assert rule_ids(findings) == ["unguarded-shared-state"]


def test_unguarded_shared_state_positional_target(tmp_path):
    # Thread(group, target, ...) — the positional spelling races
    # exactly like target=
    src = _RACE.replace(
        "threading.Thread(target=self._work, daemon=True).start()",
        "threading.Thread(None, self._work, daemon=True).start()")
    findings = lint_src(tmp_path, src,
                        select=["unguarded-shared-state"])
    assert rule_ids(findings) == ["unguarded-shared-state"]


def test_unguarded_shared_state_quiet_when_locked(tmp_path):
    src = _RACE.replace(
        "        self.value = 1",
        "        with self._lock:\n            self.value = 1"
    ).replace(
        "        self.value = v",
        "        with self._lock:\n            self.value = v")
    assert lint_src(tmp_path, src,
                    select=["unguarded-shared-state"]) == []


def test_unguarded_shared_state_across_inheritance(tmp_path):
    # base class starts the thread, SUBCLASS adds the racing public
    # method — per-class pairing used to lint this clean
    src = """\
import threading


class Base:
    def __init__(self):
        threading.Thread(target=self._loop, daemon=True).start()

    def _loop(self):
        self.x = 1


class Api(Base):
    def set_x(self, v):
        self.x = v
"""
    findings = lint_src(tmp_path, src,
                        select=["unguarded-shared-state"])
    assert rule_ids(findings) == ["unguarded-shared-state"]
    assert ".x is written" in findings[0].message


def test_unguarded_shared_state_honours_inherited_lock(tmp_path):
    # writes guarded by a lock the BASE class bound must count as
    # locked, not fire as false positives
    src = """\
import threading


class Base:
    def __init__(self):
        self._lock = threading.Lock()


class W(Base):
    def __init__(self):
        super().__init__()
        threading.Thread(target=self._work, daemon=True).start()

    def _work(self):
        with self._lock:
            self.value = 1

    def push(self, v):
        with self._lock:
            self.value = v
"""
    assert lint_src(tmp_path, src,
                    select=["unguarded-shared-state"]) == []


# -- checkpoint-state --------------------------------------------------

_STATEFUL_UNIT = """\
class Counter(Unit):
    def run(self):
        self.count = getattr(self, "count", 0) + 1
"""


def test_checkpoint_state_fires(tmp_path):
    findings = lint_src(tmp_path, _STATEFUL_UNIT,
                        select=["checkpoint-state"])
    assert rule_ids(findings) == ["checkpoint-state"]
    assert "Counter.run() mutates self.count" in findings[0].message


def test_checkpoint_state_quiet_with_get_state_or_pragma(tmp_path):
    with_state = _STATEFUL_UNIT + (
        "\n    def get_state(self):\n"
        "        return {\"count\": self.count}\n")
    assert lint_src(tmp_path, with_state,
                    select=["checkpoint-state"]) == []
    pragma = _STATEFUL_UNIT.replace(
        "class Counter(Unit):",
        "class Counter(Unit):  "
        "# zlint: disable=checkpoint-state (ephemeral demo)")
    assert lint_src(tmp_path, pragma,
                    select=["checkpoint-state"]) == []


def test_checkpoint_state_inherited_get_state_counts(tmp_path):
    src = """\
class Base(Unit):
    def get_state(self):
        return {}


class Derived(Base):
    def run(self):
        self.n = 1
"""
    assert lint_src(tmp_path, src, select=["checkpoint-state"]) == []


# -- telemetry-hygiene -------------------------------------------------


def test_telemetry_hygiene_loop_creation_fires(tmp_path):
    src = """\
from veles import telemetry


def hot(n):
    for i in range(n):
        telemetry.counter("veles_x_total", "help").inc()
"""
    findings = lint_src(tmp_path, src, select=["telemetry-hygiene"])
    assert rule_ids(findings) == ["telemetry-hygiene"]
    assert "inside a loop" in findings[0].message
    hoisted = """\
from veles import telemetry


def hot(n):
    c = telemetry.counter("veles_x_total", "help")
    for i in range(n):
        c.inc()
"""
    assert lint_src(tmp_path, hoisted,
                    select=["telemetry-hygiene"]) == []


def test_telemetry_hygiene_formatted_name_in_loop_fires(tmp_path):
    # a name formatted per iteration leaks one family per value —
    # the WORSE failure mode must not be exempt from the loop check
    src = """\
from veles import telemetry


def leak(names):
    for n in names:
        telemetry.counter("veles_%s_total" % n, "help").inc()
"""
    findings = lint_src(tmp_path, src, select=["telemetry-hygiene"])
    assert rule_ids(findings) == ["telemetry-hygiene"]


def test_telemetry_hygiene_sees_registry_handle_style(tmp_path):
    # `reg = telemetry.get_registry()` handles are what the runtime
    # actually uses — the loop check must reach them too
    src = """\
from veles import telemetry


def leak(names):
    reg = telemetry.get_registry()
    for n in names:
        reg.counter("veles_%s_total" % n, "help").inc()
"""
    findings = lint_src(tmp_path, src, select=["telemetry-hygiene"])
    assert rule_ids(findings) == ["telemetry-hygiene"]


def test_telemetry_hygiene_identity_label_fires(tmp_path):
    src = """\
def label_it(fam, obj):
    fam.labels(id(obj)).inc()
"""
    findings = lint_src(tmp_path, src, select=["telemetry-hygiene"])
    assert rule_ids(findings) == ["telemetry-hygiene"]
    assert "identity" in findings[0].message
    bounded = """\
def label_it(fam, kind):
    fam.labels(kind).inc()
"""
    assert lint_src(tmp_path, bounded,
                    select=["telemetry-hygiene"]) == []


def test_telemetry_hygiene_identity_span_name_fires(tmp_path):
    # the tracing twin of identity labels (ISSUE 6 satellite): a span
    # NAME minted per request is unbounded name cardinality — every
    # formatted spelling must fire, across receiver shapes
    src = """\
from veles import telemetry


def serve(tracer, job_id, token):
    with telemetry.span("job-%s" % job_id):
        pass
    with tracer.span(f"req.{token}"):
        pass
    telemetry.tracer.add_complete("j.{}".format(job_id), 0.0, 1.0)
"""
    findings = lint_src(tmp_path, src, select=["telemetry-hygiene"])
    assert rule_ids(findings) == ["telemetry-hygiene"] * 3
    assert "span name" in findings[0].message


def test_telemetry_hygiene_span_identity_in_args_quiet(tmp_path):
    # the sanctioned spelling: constant name, identity in the ARGS —
    # and non-identity formatting (unit/kind names) stays legal
    src = """\
from veles import telemetry


def serve(tracer, job_id, kind):
    with telemetry.span("job.serve", job_id=job_id):
        pass
    telemetry.tracer.add_complete("xla.dispatch.%s" % kind, 0.0, 1.0)


class Unit:
    def run(self, tracer):
        tracer.add_complete("%s.run" % self.name, 0.0, 1.0)
"""
    assert lint_src(tmp_path, src,
                    select=["telemetry-hygiene"]) == []


def test_telemetry_hygiene_wire_label_fires(tmp_path):
    # ISSUE 18: a .labels(...) value read straight off the wire lets
    # callers mint series at will — every spelling of the read fires
    src = """\
def count(fam, request):
    fam.labels(request.headers.get("x-veles-tenant")).inc()
    fam.labels(request.headers["x-api-key"]).inc()
    fam.labels("t-%s" % request.body).inc()
"""
    findings = lint_src(tmp_path, src, select=["telemetry-hygiene"])
    assert rule_ids(findings) == ["telemetry-hygiene"] * 3
    assert "headers/body" in findings[0].message
    assert "resolve" in findings[0].hint


def test_telemetry_hygiene_wire_label_resolver_quiet(tmp_path):
    # the sanctioned spelling: the raw header passes through a
    # bounded *resolve* call (unknown keys fold to one bucket), or is
    # resolved into a plain local before labelling
    src = """\
def count(fam, table, request):
    fam.labels(table.resolve(request.headers.get("x-tenant"))).inc()
    tenant = table.resolve(request.headers.get("x-tenant"))
    fam.labels(tenant).inc()
"""
    assert lint_src(tmp_path, src,
                    select=["telemetry-hygiene"]) == []


def test_telemetry_hygiene_wire_label_pragma(tmp_path):
    src = """\
def count(fam, request):
    fam.labels(request.headers.get("x-t")).inc()  \
# zlint: disable=telemetry-hygiene (bounded by proxy upstream)
"""
    assert lint_src(tmp_path, src,
                    select=["telemetry-hygiene"]) == []


def test_telemetry_hygiene_span_rule_ignores_foreign_span(tmp_path):
    # .span on a non-telemetry receiver (e.g. a regex Match.span or a
    # geometry object) must not fire, whatever the argument looks like
    src = """\
def shape(layout, col_id):
    return layout.span("cell-%s" % col_id)
"""
    assert lint_src(tmp_path, src,
                    select=["telemetry-hygiene"]) == []


# -- thread-lifecycle --------------------------------------------------


def test_thread_lifecycle_fires_without_daemon_or_join(tmp_path):
    src = """\
import threading


def spawn(work):
    t = threading.Thread(target=work)
    t.start()
"""
    findings = lint_src(tmp_path, src, select=["thread-lifecycle"])
    assert rule_ids(findings) == ["thread-lifecycle"]


def test_thread_lifecycle_sees_aliased_threading_module(tmp_path):
    src = """\
import threading as th


def spawn(work):
    th.Thread(target=work).start()
"""
    findings = lint_src(tmp_path, src, select=["thread-lifecycle"])
    assert rule_ids(findings) == ["thread-lifecycle"]
    # other modules' Thread attribute is NOT the constructor
    other = """\
import notthreading


def spawn(work):
    notthreading.Thread(target=work).start()
"""
    assert lint_src(tmp_path, other,
                    select=["thread-lifecycle"]) == []


def test_thread_lifecycle_quiet_on_daemon_or_join(tmp_path):
    daemon = """\
import threading


def spawn(work):
    threading.Thread(target=work, daemon=True).start()
"""
    assert lint_src(tmp_path, daemon,
                    select=["thread-lifecycle"]) == []
    joined = """\
import threading


def spawn(work):
    t = threading.Thread(target=work)
    t.start()
    t.join()
"""
    assert lint_src(tmp_path, joined,
                    select=["thread-lifecycle"]) == []
    # `t.daemon = True` before start() is the standard idiom and
    # just as shutdown-safe as the constructor keyword
    attr_daemon = """\
import threading


def spawn(work):
    t = threading.Thread(target=work)
    t.daemon = True
    t.start()
"""
    assert lint_src(tmp_path, attr_daemon,
                    select=["thread-lifecycle"]) == []


# -- probe-purity ------------------------------------------------------

_PROBE_BAD = """\
import urllib.request


class Handler:
    def do_GET(self):
        if self.path == "/healthz":
            with self.server.lock:
                doc = self.master.status()
            self._reply(200, doc)
        elif self.path.startswith("/readyz"):
            body = urllib.request.urlopen(
                "http://peer:8080/metrics").read()
            self._reply(200, body)
        else:
            self._reply(404, {})
"""

_PROBE_GOOD = """\
class Handler:
    def do_GET(self):
        if self.path.startswith(("/healthz", "/readyz")):
            code, doc = monitor.probe(self.path)
            self._reply(code, doc)
        elif self.path.startswith("/metrics"):
            with self.lock:
                body = registry.render_prometheus()
            self._reply(200, body)
        else:
            self._reply(404, {})
"""


def test_probe_purity_fires_on_blocking_probe_branches(tmp_path):
    """Satellite (ISSUE 8): /healthz taking a lock + pulling live
    status, /readyz fetching over the network — every blocking shape
    fires; the hint points at the cached-verdict contract."""
    findings = lint_src(tmp_path, _PROBE_BAD, select=["probe-purity"])
    assert set(rule_ids(findings)) == {"probe-purity"}
    messages = " | ".join(f.message for f in findings)
    assert "context-managed" in messages       # the with-lock
    assert "'status'" in messages              # the live state pull
    assert "'urlopen'" in messages             # the network fetch
    assert len(findings) >= 3


def test_probe_purity_quiet_on_cached_reads_and_other_routes(tmp_path):
    """The compliant shape — probe branches read the monitor's cached
    verdict — is quiet, and a with-lock in a NON-probe branch
    (/metrics) is out of scope for this rule."""
    assert lint_src(tmp_path, _PROBE_GOOD,
                    select=["probe-purity"]) == []


def test_probe_purity_pragma_suppresses(tmp_path):
    src = """\
class Handler:
    def do_GET(self):
        if self.path == "/healthz":
            with self.lock:  # zlint: disable=probe-purity (test rig)
                doc = dict(self.cache)
            self._reply(200, doc)
"""
    assert lint_src(tmp_path, src, select=["probe-purity"]) == []


# -- reactor-purity ----------------------------------------------------

_REACTOR_BAD = """\
import time
from urllib.request import urlopen


class Session:
    def on_frame(self, obj):
        time.sleep(0.1)                 # parks the whole loop
        return self.sock.recv(4096)     # raw-socket wait


class Plane:
    def __init__(self, loop):
        loop.call_soon(self._merge)
        loop.every(1.0, self._sweep)
        loop.call_later(0.5, lambda: self.done.wait())

    def _merge(self):
        self.worker.join()              # Thread.join shape
        return urlopen("http://127.0.0.1:1/metrics")

    def _sweep(self):
        self.sock.sendall(b"tick")
"""

_REACTOR_GOOD = """\
class Session:
    def on_frame(self, obj):
        resp = self.handle(obj)
        with self.lock:                 # existing lock discipline: ok
            self.counter += 1
        self.send_obj(resp)
        return ", ".join(str(x) for x in resp)   # str.join, not Thread


class Plane:
    def __init__(self, loop):
        loop.call_soon(self._merge, 1)
        loop.every(1.0, self._sweep)

    def _merge(self, n):
        self.pending.append(n)

    def _sweep(self):
        for conn in self.connections():
            if conn.stale:
                conn.close()

    def off_loop_helper(self):
        # NOT a reactor callback: blocking here is the worker
        # thread's whole job
        self.done.wait(2.0)
"""


def test_reactor_purity_fires_on_blocking_callbacks(tmp_path):
    """Satellite (ISSUE 9): sleep + raw recv inside on_frame, and
    join/urlopen/sendall/Event.wait inside call_soon/every/call_later
    targets (incl. a lambda) all fire."""
    findings = lint_src(tmp_path, _REACTOR_BAD,
                        select=["reactor-purity"])
    assert set(rule_ids(findings)) == {"reactor-purity"}
    messages = " | ".join(f.message for f in findings)
    for name in ("'sleep'", "'recv'", "'join'", "'urlopen'",
                 "'sendall'", "'wait'"):
        assert name in messages, (name, messages)
    assert len(findings) >= 6


def test_reactor_purity_quiet_on_pure_callbacks(tmp_path):
    """The compliant shapes are quiet: locks (the existing handle()
    discipline), str.join, queue appends, conn.close sweeps — and
    blocking calls in methods that are NOT reactor callbacks are out
    of scope."""
    assert lint_src(tmp_path, _REACTOR_GOOD,
                    select=["reactor-purity"]) == []


def test_reactor_purity_pragma_suppresses(tmp_path):
    src = """\
import time


class S:
    def on_timer(self):
        time.sleep(0.01)  # zlint: disable=reactor-purity (test rig)
"""
    assert lint_src(tmp_path, src, select=["reactor-purity"]) == []


# -- profiler-safety ---------------------------------------------------

_PROFILER_BAD = """\
from veles import profiling


class Status:
    def _route(self, request):
        if request.path.startswith("/debug/profile"):
            code, body, ctype = profiling.profile_endpoint(
                request.path)
            request.reply(code, body, ctype)


class Wire:
    def on_frame(self, obj):
        self.profiler.start()
        prof = profiling.capture_profile(2.0)
        self.profiler.stop()
        return prof


class Plane:
    def __init__(self, loop, profiler):
        loop.every(1.0, self._tick)
        self._profiler = profiler

    def _tick(self):
        self._profiler.capture()
"""

_PROFILER_GOOD = """\
from veles import profiling


class Status:
    def _route(self, request):
        if request.path.startswith("/debug/profile"):
            request.defer(self._serve_profile, request)
        elif request.path.startswith("/debug/"):
            request.reply_json(200, {})

    def _serve_profile(self, request):
        # worker thread: blocking here is the whole point
        code, body, ctype = profiling.profile_endpoint(request.path)
        request.reply(code, body, ctype)


def bench_row():
    # NOT a reactor callback or route: a bench/CLI capture is fine
    profiler = profiling.SamplingProfiler()
    profiler.start()
    profiler.stop()
    return profiler.profile()


class Wire:
    def on_frame(self, obj):
        # unrelated receivers named start/stop stay quiet
        self.timer.start()
        self.timer.stop()
"""


def test_profiler_safety_fires_on_inline_captures(tmp_path):
    """Satellite (ISSUE 10): a /debug/profile branch answering inline
    (no defer, direct profile_endpoint), profiler start/stop +
    capture_profile inside on_frame, and .capture() inside an every()
    target all fire."""
    findings = lint_src(tmp_path, _PROFILER_BAD,
                        select=["profiler-safety"])
    assert set(rule_ids(findings)) == {"profiler-safety"}
    messages = " | ".join(f.message for f in findings)
    assert "'profile_endpoint'" in messages      # inline route call
    assert "'capture_profile'" in messages       # on_frame capture
    assert "profiler.start" in messages          # start on the loop
    assert "_profiler.capture" in messages       # scheduled target
    assert len(findings) >= 5


def test_profiler_safety_quiet_on_deferred_and_offloop(tmp_path):
    """The compliant shapes: the route branch defers to a worker (the
    blocking body lives in the deferred method), a bench/CLI capture
    off the loop, and non-profiler .start()/.stop() receivers."""
    assert lint_src(tmp_path, _PROFILER_GOOD,
                    select=["profiler-safety"]) == []


def test_profiler_safety_pragma_suppresses(tmp_path):
    src = """\
class S:
    def on_timer(self):
        self.profiler.start()  # zlint: disable=profiler-safety (rig)
"""
    assert lint_src(tmp_path, src, select=["profiler-safety"]) == []


# -- wire-schema -------------------------------------------------------

_WIRE_MISMATCH = """\
def recv_frame(sock):
    return sock


class Master:
    def handle(self, request):
        if request[0] == "job":
            return ("job", request, 1, 2, 3)
        return ("ok",)


def pump(sock):
    resp = recv_frame(sock)
    if resp[0] == "job":
        _, payload, job_id, epoch = resp
        return payload, job_id, epoch
"""

_WIRE_GUARDED = """\
def recv_frame(sock):
    return sock


class Master:
    def handle(self, request):
        if request[0] == "job":
            return ("job", request, 1, 2, 3)
        return ("ok",)


def pump(sock):
    resp = recv_frame(sock)
    if resp[0] != "job" or len(resp) < 4:
        return None
    _, payload, job_id, epoch = resp[:4]
    trace = resp[4] if len(resp) > 4 else None
    return payload, job_id, epoch, trace


def pump_skew_tolerant(sock):
    resp = recv_frame(sock)
    if resp[0] == "job":
        try:
            _, payload, job_id = resp
        except ValueError:
            return None
        return payload, job_id
"""


def test_wire_schema_arity_mismatch_fires(tmp_path):
    """The seeded mismatch (ISSUE 12 satellite): producer ships a
    5-tuple, consumer tuple-unpacks 4 without a slice guard."""
    findings = lint_src(tmp_path, _WIRE_MISMATCH,
                        select=["wire-schema"])
    assert rule_ids(findings) == ["wire-schema"]
    assert "5-tuple" in findings[0].message
    assert "ValueError" in findings[0].message


def test_wire_schema_index_past_producer_fires(tmp_path):
    src = _WIRE_MISMATCH.replace(
        "        _, payload, job_id, epoch = resp\n"
        "        return payload, job_id, epoch",
        "        return resp[5]")
    findings = lint_src(tmp_path, src, select=["wire-schema"])
    assert rule_ids(findings) == ["wire-schema"]
    assert "element 5" in findings[0].message


def test_wire_schema_quiet_on_guarded_consumers(tmp_path):
    """Every mixed-version-safe spelling stays quiet: the early-exit
    len guard + slice unpack, the conditional-expression len guard,
    and the try/except ValueError skew handler."""
    assert lint_src(tmp_path, _WIRE_GUARDED,
                    select=["wire-schema"]) == []


def test_wire_schema_directions_are_separate_namespaces(tmp_path):
    # the request ("job", sid, lease) 3-tuple and the response
    # ("job", payload, job_id, epoch, trace) 5-tuple share a kind;
    # a response consumer must be judged against response producers
    # only, or every protocol with symmetric kinds false-positives
    src = """\
def send_frame(sock, obj):
    pass


def recv_frame(sock):
    return sock


class Master:
    def handle(self, request):
        if request[0] == "job":
            return ("job", request, 1, 2, 3)
        return ("ok",)


def pump(sock):
    send_frame(sock, ("job", 7, "lease"))
    resp = recv_frame(sock)
    if resp[0] != "job" or len(resp) < 4:
        return None
    _, payload, job_id, epoch = resp[:4]
    return payload, job_id, epoch
"""
    assert lint_src(tmp_path, src, select=["wire-schema"]) == []


def test_wire_schema_no_producer_is_quiet(tmp_path):
    # a kind the analyzer never sees produced (an external peer)
    # cannot be judged — arbitrary [0] == "str" code must not fire
    src = """\
def route(argv):
    if argv[0] == "serve":
        return argv[1]
"""
    assert lint_src(tmp_path, src, select=["wire-schema"]) == []


def test_wire_schema_floor_guard_excludes_short_producers(tmp_path):
    # mixed-version producers (2-tuple and 4-tuple welcome): the
    # canonical `len(resp) < 4: return` guard makes the short
    # variant unreachable at the unpack, so the exact unpack of 4
    # must be judged against the 4-tuple producer only
    src = """\
def recv_frame(sock):
    return sock


class Master:
    def handle(self, request):
        if len(request) < 3:
            return ("welcome", 1)
        return ("welcome", 1, 2, 3)


def connect(sock):
    resp = recv_frame(sock)
    if resp[0] != "welcome" or len(resp) < 4:
        return None
    _, a, b, c = resp
    return a, b, c
"""
    assert lint_src(tmp_path, src, select=["wire-schema"]) == []


def test_wire_schema_pragma_suppresses(tmp_path):
    src = _WIRE_MISMATCH.replace(
        "        _, payload, job_id, epoch = resp",
        "        _, payload, job_id, epoch = resp  "
        "# zlint: disable=wire-schema (peer ships 4)")
    assert lint_src(tmp_path, src, select=["wire-schema"]) == []


# -- resource-leak -----------------------------------------------------

_LEAK_ON_EXC = """\
import socket


def build():
    return 1


def fetch(addr):
    sock = socket.create_connection(addr)
    meta = build()
    sock.close()
    return meta
"""

_LEAK_SAFE = """\
import socket


def build():
    return 1


def fetch(addr):
    sock = socket.create_connection(addr)
    try:
        meta = build()
    finally:
        sock.close()
    return meta


def fetch_handler(addr):
    sock = socket.create_connection(addr)
    try:
        meta = build()
    except OSError:
        sock.close()
        raise
    sock.close()
    return meta


def stored(self, addr):
    sock = socket.create_connection(addr)
    self.sock = sock
    return self


def handed_off(addr, conns):
    sock = socket.create_connection(addr)
    conns.append(sock)
"""


def test_resource_leak_on_exception_path_fires(tmp_path):
    """The leak-on-exception fixture (ISSUE 12 satellite): the bench
    MasterServer class of bug — a risky call between acquire and
    release with no try/finally."""
    findings = lint_src(tmp_path, _LEAK_ON_EXC,
                        select=["resource-leak"])
    assert rule_ids(findings) == ["resource-leak"]
    assert "build()" in findings[0].message
    assert findings[0].line == 9          # anchored at the acquire


def test_resource_leak_never_released_fires(tmp_path):
    src = """\
import socket


def probe(addr):
    sock = socket.create_connection(addr)
    return sock.getpeername()[0]
"""
    findings = lint_src(tmp_path, src, select=["resource-leak"])
    assert rule_ids(findings) == ["resource-leak"]
    assert "never released" in findings[0].message


def test_resource_leak_discarded_grant_fires(tmp_path):
    src = """\
def admit(pool):
    pool.grant()
"""
    findings = lint_src(tmp_path, src, select=["resource-leak"])
    assert rule_ids(findings) == ["resource-leak"]
    assert "discarded" in findings[0].message


def test_resource_leak_quiet_on_safe_shapes(tmp_path):
    """try/finally, except-release-reraise, attribute store and
    container hand-off all own the resource correctly."""
    assert lint_src(tmp_path, _LEAK_SAFE,
                    select=["resource-leak"]) == []


def test_resource_leak_quiet_on_with_and_slot_store(tmp_path):
    src = """\
def read(path, pool, active, req):
    with open(path) as f:
        data = f.read()
    req.slot = pool.grant()
    active[req.slot] = req
    return data
"""
    assert lint_src(tmp_path, src, select=["resource-leak"]) == []


def test_resource_leak_sibling_branch_is_not_a_path(tmp_path):
    # the else-arm of the acquiring if is mutually exclusive with
    # the acquisition — its calls are not on any path where the
    # resource is live
    src = """\
import socket


def make_other():
    return None


def connect(addr, fast):
    if fast:
        sock = socket.create_connection(addr)
    else:
        sock = make_other()
    try:
        data = sock.recv(1)
    finally:
        sock.close()
    return data
"""
    assert lint_src(tmp_path, src, select=["resource-leak"]) == []


def test_resource_leak_pragma_suppresses(tmp_path):
    src = _LEAK_ON_EXC.replace(
        "    sock = socket.create_connection(addr)",
        "    sock = socket.create_connection(addr)  "
        "# zlint: disable=resource-leak (test rig)")
    assert lint_src(tmp_path, src, select=["resource-leak"]) == []


# -- loop-exception-safety ---------------------------------------------

_LOOP_RAISE = """\
class Session:
    def on_frame(self, obj):
        self.dispatch(obj)

    def dispatch(self, obj):
        if not obj:
            raise ValueError("empty frame")
        return obj
"""

_LOOP_SAFE = """\
class Session:
    def on_frame(self, obj):
        try:
            self.dispatch(obj)
        except (ValueError, KeyError):
            self.reply_error()

    def dispatch(self, obj):
        if not obj:
            raise ValueError("empty frame")
        return obj

    def reply_error(self):
        pass


class Stub:
    def on_frame(self, obj):
        raise NotImplementedError


class Fenced(ConnectionError):
    pass


class Plane:
    def __init__(self, loop):
        loop.every(1.0, self._tick)

    def _tick(self):
        try:
            self.sync()
        except OSError:
            pass

    def sync(self):
        raise Fenced("lease revoked")
"""


def test_loop_exception_uncaught_chain_fires(tmp_path):
    findings = lint_src(tmp_path, _LOOP_RAISE,
                        select=["loop-exception-safety"])
    assert rule_ids(findings) == ["loop-exception-safety"]
    assert "ValueError" in findings[0].message
    assert "Session.on_frame -> Session.dispatch" \
        in findings[0].message


def test_loop_exception_scheduled_target_fires(tmp_path):
    src = """\
class Plane:
    def __init__(self, loop):
        loop.every(1.0, self._tick)

    def _tick(self):
        raise RuntimeError("wedged")
"""
    findings = lint_src(tmp_path, src,
                        select=["loop-exception-safety"])
    assert rule_ids(findings) == ["loop-exception-safety"]
    assert "RuntimeError" in findings[0].message


def test_loop_exception_quiet_on_caught_chains(tmp_path):
    """A try anywhere on the chain covers the raise — including
    through the exception HIERARCHY (a ConnectionError subclass is
    caught by except OSError) — and NotImplementedError stubs are
    the abstract-method convention, not a loop hazard."""
    assert lint_src(tmp_path, _LOOP_SAFE,
                    select=["loop-exception-safety"]) == []


def test_loop_exception_handler_body_is_outside_its_try(tmp_path):
    # a raise INSIDE the except handler is not protected by the
    # handler's own try — the classic error-path-raises bug
    src = """\
class Session:
    def on_frame(self, obj):
        try:
            self.dispatch(obj)
        except ValueError:
            raise RuntimeError("bad frame")

    def dispatch(self, obj):
        return obj
"""
    findings = lint_src(tmp_path, src,
                        select=["loop-exception-safety"])
    assert rule_ids(findings) == ["loop-exception-safety"]
    assert "RuntimeError" in findings[0].message


def test_loop_exception_pragma_suppresses(tmp_path):
    src = _LOOP_RAISE.replace(
        '            raise ValueError("empty frame")',
        '            raise ValueError("empty frame")  '
        '# zlint: disable=loop-exception-safety (severing intended)')
    assert lint_src(tmp_path, src,
                    select=["loop-exception-safety"]) == []


# -- hygiene: bare-except / unused-import / unused-variable ------------


def test_bare_except_fires_and_named_is_quiet(tmp_path):
    src = "try:\n    pass\nexcept:\n    pass\n"
    findings = lint_src(tmp_path, src, select=["bare-except"])
    assert rule_ids(findings) == ["bare-except"]
    named = src.replace("except:", "except Exception:")
    assert lint_src(tmp_path, named, select=["bare-except"]) == []


def test_unused_import_fires_and_noqa_is_quiet(tmp_path):
    src = "import os\nimport sys\n\nprint(sys.argv)\n"
    findings = lint_src(tmp_path, src, select=["unused-import"])
    assert ["unused-import"] == rule_ids(findings)
    assert "'os'" in findings[0].message
    noqa = src.replace("import os", "import os  # noqa: F401")
    assert lint_src(tmp_path, noqa, select=["unused-import"]) == []
    # __init__.py is a re-export surface: exempt wholesale
    assert lint_src(tmp_path, src, relname="pkg/__init__.py",
                    select=["unused-import"]) == []


def test_unused_variable_fires_and_exemptions_hold(tmp_path):
    src = """\
def f(x):
    dead = x + 1
    return x
"""
    findings = lint_src(tmp_path, src, select=["unused-variable"])
    assert rule_ids(findings) == ["unused-variable"]
    assert "'dead'" in findings[0].message
    # underscore names, closure reads and locals() users are exempt
    quiet = """\
def f(x):
    _dead = x + 1
    kept = x + 2

    def g():
        return kept
    return g


def h(x):
    maybe_dead = x
    return locals()
"""
    assert lint_src(tmp_path, quiet, select=["unused-variable"]) == []


# -- pragma engine -----------------------------------------------------


def test_pragma_disable_all_and_multi_rule(tmp_path):
    src = ("try:\n    pass\n"
           "except:  # zlint: disable=all (fixture)\n    pass\n")
    assert lint_src(tmp_path, src, select=["bare-except"]) == []
    multi = ("try:\n    pass\n"
             "except:  # zlint: disable=unused-import,bare-except\n"
             "    pass\n")
    assert lint_src(tmp_path, multi, select=["bare-except"]) == []


def test_pragma_inside_string_literal_is_not_a_pragma(tmp_path):
    src = ('S = "# zlint: disable=bare-except"\n'
           "try:\n    pass\nexcept:\n    pass\n")
    findings = lint_src(tmp_path, src, select=["bare-except"])
    assert rule_ids(findings) == ["bare-except"]


def test_pragma_on_other_line_does_not_suppress(tmp_path):
    src = ("# zlint: disable=bare-except\n"
           "try:\n    pass\nexcept:\n    pass\n")
    findings = lint_src(tmp_path, src, select=["bare-except"])
    assert rule_ids(findings) == ["bare-except"]


# -- CLI contract ------------------------------------------------------


def test_cli_exit_codes(tmp_path, capsys):
    clean = tmp_path / "clean.py"
    clean.write_text("X = 1\n")
    assert lint_main([str(clean)]) == 0
    dirty = tmp_path / "dirty.py"
    dirty.write_text("try:\n    pass\nexcept:\n    pass\n")
    assert lint_main([str(dirty)]) == 1
    out = capsys.readouterr().out
    assert "bare-except" in out and "1 finding(s)" in out
    assert lint_main([str(tmp_path / "missing.py")]) == 2
    assert lint_main(["--select", "no-such-rule", str(clean)]) == 2
    # an unparseable input is a usage error, NOT a "findings" verdict
    broken = tmp_path / "broken.py"
    broken.write_text("def f(:\n")
    capsys.readouterr()
    assert lint_main([str(broken)]) == 2
    assert "cannot parse" in capsys.readouterr().err


def test_cli_unreadable_input_is_usage_error(tmp_path, monkeypatch,
                                             capsys):
    # PermissionError (or any transient FS failure) must exit 2, not
    # traceback with the "findings" code 1
    import builtins
    target = tmp_path / "locked.py"
    target.write_text("X = 1\n")
    real_open = builtins.open

    def deny(path, *args, **kwargs):
        if str(path) == str(target):
            raise PermissionError(13, "Permission denied", str(path))
        return real_open(path, *args, **kwargs)

    monkeypatch.setattr(builtins, "open", deny)
    assert lint_main([str(target)]) == 2
    assert "cannot read" in capsys.readouterr().err


def test_cli_json_is_sorted_and_shaped(tmp_path, capsys):
    a = tmp_path / "a.py"
    a.write_text("import os\n\ntry:\n    pass\nexcept:\n    pass\n")
    b = tmp_path / "b.py"
    b.write_text("try:\n    pass\nexcept:\n    pass\n")
    cwd = os.getcwd()
    os.chdir(tmp_path)        # repo-relative paths in the output
    try:
        rc = lint_main(["--json", str(a), str(b)])
    finally:
        os.chdir(cwd)
    assert rc == 1
    payload = json.loads(capsys.readouterr().out)
    assert [sorted(f) for f in payload] == [
        ["file", "hint", "line", "message", "rule", "severity"]
    ] * len(payload)
    keys = [(f["file"], f["line"], f["rule"]) for f in payload]
    assert keys == sorted(keys), "JSON findings must be CI-diffable"
    assert all(not os.path.isabs(f["file"]) for f in payload)
    # byte-stable across runs
    os.chdir(tmp_path)
    try:
        lint_main(["--json", str(a), str(b)])
    finally:
        os.chdir(cwd)
    assert json.loads(capsys.readouterr().out) == payload


def test_cli_list_rules_names_every_registered_rule(capsys):
    from veles.analysis import RULES
    assert lint_main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rule_id in ("tracer-purity", "lock-order",
                    "unguarded-shared-state", "checkpoint-state",
                    "telemetry-hygiene", "thread-lifecycle",
                    "wire-schema", "resource-leak",
                    "loop-exception-safety",
                    "bare-except", "unused-import", "unused-variable"):
        assert rule_id in out
        assert rule_id in RULES


def test_cli_sarif_shape_and_stability(tmp_path, capsys):
    """--format sarif: a valid SARIF 2.1.0 skeleton (ruleId, level,
    artifactLocation/region anchors, the rule table), byte-stable
    across runs, exit-code contract unchanged."""
    p = tmp_path / "m.py"
    p.write_text("try:\n    pass\nexcept:\n    pass\n")
    cwd = os.getcwd()
    os.chdir(tmp_path)
    try:
        rc = lint_main(["--format", "sarif", str(p)])
        first = capsys.readouterr().out
        rc2 = lint_main(["--format", "sarif", str(p)])
        second = capsys.readouterr().out
    finally:
        os.chdir(cwd)
    assert rc == 1 and rc2 == 1
    assert first == second, "SARIF must be byte-stable"
    doc = json.loads(first)
    assert doc["version"] == "2.1.0"
    run = doc["runs"][0]
    assert run["tool"]["driver"]["name"] == "zlint"
    assert [r["id"] for r in run["tool"]["driver"]["rules"]] \
        == ["bare-except"]
    result = run["results"][0]
    assert result["ruleId"] == "bare-except"
    assert result["level"] == "error"
    loc = result["locations"][0]["physicalLocation"]
    assert loc["artifactLocation"]["uri"] == "m.py"
    assert loc["region"]["startLine"] == 3
    assert "hint:" in result["message"]["text"]
    # clean tree: rc 0, empty results, still valid SARIF
    clean = tmp_path / "clean.py"
    clean.write_text("X = 1\n")
    assert lint_main(["--format", "sarif", str(clean)]) == 0
    empty = json.loads(capsys.readouterr().out)
    assert empty["runs"][0]["results"] == []


def test_cli_json_flag_is_format_alias(tmp_path, capsys):
    p = tmp_path / "m.py"
    p.write_text("try:\n    pass\nexcept:\n    pass\n")
    assert lint_main(["--json", str(p)]) == 1
    legacy = capsys.readouterr().out
    assert lint_main(["--format", "json", str(p)]) == 1
    assert capsys.readouterr().out == legacy


def _git(tmp_path, *argv):
    import subprocess
    return subprocess.run(
        ["git", "-c", "user.email=t@t", "-c", "user.name=t"]
        + list(argv), cwd=tmp_path, check=True, capture_output=True)


def test_cli_changed_only_lints_only_changed_files(tmp_path, capsys):
    """--changed-only: the committed-but-unchanged violation is
    skipped, the modified and the untracked files are linted; exit
    codes keep the 0/1 contract."""
    _git(tmp_path, "init", "-q")
    a = tmp_path / "a.py"
    a.write_text("try:\n    pass\nexcept:\n    pass\n")
    b = tmp_path / "b.py"
    b.write_text("X = 1\n")
    _git(tmp_path, "add", ".")
    _git(tmp_path, "commit", "-qm", "seed")
    b.write_text("try:\n    pass\nexcept:\n    pass\n")
    c = tmp_path / "c.py"                 # untracked
    c.write_text("try:\n    pass\nexcept:\n    pass\n")
    cwd = os.getcwd()
    os.chdir(tmp_path)
    try:
        rc = lint_main([str(tmp_path), "--changed-only",
                        "--select", "bare-except"])
        out = capsys.readouterr().out
        # with nothing changed vs HEAD the changed set is empty:
        # clean exit, zero findings
        _git(tmp_path, "add", ".")
        _git(tmp_path, "commit", "-qm", "all dirty now clean")
        rc_clean = lint_main([str(tmp_path), "--changed-only",
                              "--select", "bare-except"])
        out_clean = capsys.readouterr().out
    finally:
        os.chdir(cwd)
    assert rc == 1
    assert "b.py" in out and "c.py" in out
    assert "a.py" not in out
    assert rc_clean == 0 and "0 finding(s)" in out_clean


def test_cli_changed_only_bad_ref_is_usage_error(tmp_path, capsys):
    # a typo'd ref must hit the documented exit-2 contract, never
    # silently degrade to a full-tree run
    _git(tmp_path, "init", "-q")
    p = tmp_path / "a.py"
    p.write_text("X = 1\n")
    _git(tmp_path, "add", ".")
    _git(tmp_path, "commit", "-qm", "seed")
    cwd = os.getcwd()
    os.chdir(tmp_path)
    try:
        rc = lint_main([str(tmp_path), "--changed-only",
                        "no-such-ref"])
    finally:
        os.chdir(cwd)
    assert rc == 2
    assert "cannot resolve ref" in capsys.readouterr().err


def test_cli_changed_only_falls_back_without_git(tmp_path, capsys):
    # outside any repository the fast mode degrades to the full
    # tree, loudly
    p = tmp_path / "m.py"
    p.write_text("try:\n    pass\nexcept:\n    pass\n")
    cwd = os.getcwd()
    os.chdir(tmp_path)
    try:
        rc = lint_main([str(p), "--changed-only",
                        "--select", "bare-except"])
    finally:
        os.chdir(cwd)
    captured = capsys.readouterr()
    assert rc == 1
    assert "full tree" in captured.err
    assert "bare-except" in captured.out


def test_cli_select_runs_only_selected(tmp_path, capsys):
    p = tmp_path / "m.py"
    p.write_text("import os\n\ntry:\n    pass\nexcept:\n    pass\n")
    assert lint_main(["--select", "unused-import", str(p)]) == 1
    out = capsys.readouterr().out
    assert "unused-import" in out and "bare-except" not in out


# -- stats-cadence (ISSUE 15) ------------------------------------------

_STATS_BAD = """\
import numpy


class Step:
    def publish(self, outputs):
        stats = {k[5:]: v for k, v in outputs.items()
                 if k.startswith("stat/")}
        for layer, vec in stats.items():
            self.sink(layer, numpy.asarray(vec))   # per-step sync
            self.loss = float(vec[0])              # and another
"""

_STATS_GOOD = """\
import numpy


class Step:
    def _stats_due(self):
        self._tick += 1
        return self._tick % self.stats_interval == 0

    def publish(self, outputs):
        stats = {k[5:]: v for k, v in outputs.items()
                 if k.startswith("stat/")}
        if not self._stats_due():
            return
        for layer, vec in stats.items():
            self.sink(layer, numpy.asarray(vec))
"""


def test_stats_cadence_fires_on_ungated_materialization(tmp_path):
    """Satellite (ISSUE 15): a function handling "stat/"-keyed step
    outputs that materializes them (asarray + float) without ever
    consulting a stats_due gate fires once per materializer."""
    findings = lint_src(tmp_path, _STATS_BAD,
                        select=["stats-cadence"])
    assert set(rule_ids(findings)) == {"stats-cadence"}
    assert len(findings) == 2
    messages = " | ".join(f.message for f in findings)
    assert "'asarray'" in messages and "'float'" in messages
    assert "cadence" in findings[0].message


def test_stats_cadence_quiet_when_gated_and_on_sink(tmp_path):
    """The compliant shape — materialization behind a stats_due gate
    — is quiet; so is the observe_stats sink itself (every caller is
    forced through the gate) and pure key routing with no
    materializer."""
    assert lint_src(tmp_path, _STATS_GOOD,
                    select=["stats-cadence"]) == []
    sink = """\
import numpy


class Monitor:
    def observe_stats(self, layer_stats, step_index=None):
        for layer, vec in layer_stats.items():
            self.layers[layer] = float(numpy.asarray(vec)[0])
"""
    assert lint_src(tmp_path, sink, select=["stats-cadence"]) == []
    routing = """\
STAT_KEY_PREFIX = "stat/"


def take_stats(outputs):
    stats, rest = {}, {}
    for key, value in outputs.items():
        if key.startswith(STAT_KEY_PREFIX):
            stats[key[len(STAT_KEY_PREFIX):]] = value
        else:
            rest[key] = value
    return stats, rest
"""
    assert lint_src(tmp_path, routing,
                    select=["stats-cadence"]) == []


def test_stats_cadence_fires_on_sink_caller_and_pragma(tmp_path):
    """Calling the observe_stats sink marks a function stat-handling
    even without the string marker; the pragma escape works."""
    caller = """\
import numpy


class Step:
    def flush(self, vecs):
        host = [numpy.asarray(v) for v in vecs]
        self.monitor.observe_stats(dict(enumerate(host)))
"""
    findings = lint_src(tmp_path, caller, select=["stats-cadence"])
    assert rule_ids(findings) == ["stats-cadence"]
    pragma = """\
import numpy


class Step:
    def flush(self, vecs):
        host = [numpy.asarray(v) for v in vecs]  # zlint: disable=stats-cadence (one-shot postmortem dump, not a per-step path)
        self.monitor.observe_stats(dict(enumerate(host)))
"""
    assert lint_src(tmp_path, pragma,
                    select=["stats-cadence"]) == []


# -- taint rules (ISSUE 20) --------------------------------------------

_GEOM_BAD = """\
import numpy


class Proto:
    def handle(self, kind, payload):
        self.buf = numpy.zeros(payload["shape"])
"""

_GEOM_GOOD = """\
import numpy


class Proto:
    def handle(self, kind, payload):
        self.buf = numpy.zeros(
            self._validate_shape(payload["shape"]))

    def _validate_shape(self, shape):
        return [min(int(d), 64) for d in shape]
"""


def test_untrusted_geometry_fires_on_wire_shape(tmp_path):
    """A wire-handler payload sizing an allocation fires; routing it
    through a sanitizer-named bounder is quiet; a pragma'd site is
    quiet."""
    findings = lint_src(tmp_path, _GEOM_BAD,
                        select=["untrusted-geometry"])
    assert rule_ids(findings) == ["untrusted-geometry"]
    assert "wire" in findings[0].message
    assert lint_src(tmp_path, _GEOM_GOOD,
                    select=["untrusted-geometry"]) == []
    pragmad = _GEOM_BAD.replace(
        'payload["shape"])',
        'payload["shape"])  '
        '# zlint: disable=untrusted-geometry (test fixture)')
    assert lint_src(tmp_path, pragmad,
                    select=["untrusted-geometry"]) == []


def test_untrusted_geometry_crosses_calls(tmp_path):
    """Interprocedural: the handler hands its payload to a helper
    that allocates — the finding lands at the sink with the call
    chain in the message."""
    src = """\
import numpy


class Proto:
    def handle(self, kind, payload):
        self._apply(payload)

    def _apply(self, doc):
        self.buf = numpy.zeros(doc["shape"])
"""
    findings = lint_src(tmp_path, src,
                        select=["untrusted-geometry"])
    assert rule_ids(findings) == ["untrusted-geometry"]
    assert "via" in findings[0].message
    assert "handle" in findings[0].message


_CARD_BAD = """\
class Server:
    def __init__(self):
        self.stats = {}

    def handle(self, kind, payload):
        self.stats[kind] = payload
"""

_CARD_GOOD = """\
class Server:
    def __init__(self):
        self.stats = {}

    def handle(self, kind, payload):
        self.stats[self._resolve_kind(kind)] = payload

    def _resolve_kind(self, kind):
        return kind if kind in ("job", "update") else "other"
"""


def test_unbounded_cardinality_fires_on_wire_keyed_growth(tmp_path):
    findings = lint_src(tmp_path, _CARD_BAD,
                        select=["unbounded-cardinality"])
    assert rule_ids(findings) == ["unbounded-cardinality"]
    assert lint_src(tmp_path, _CARD_GOOD,
                    select=["unbounded-cardinality"]) == []
    pragmad = _CARD_BAD.replace(
        "self.stats[kind] = payload",
        "self.stats[kind] = payload  "
        "# zlint: disable=unbounded-cardinality (test fixture)")
    assert lint_src(tmp_path, pragmad,
                    select=["unbounded-cardinality"]) == []


def test_unbounded_cardinality_http_source_and_bounded_class(
        tmp_path):
    """The http taint kind (request.body) reaches the same sink; a
    container whose class is Bounded* by name is exempt."""
    src = """\
import json


class Frontend:
    def __init__(self):
        self.seen = {}

    def serve(self, request):
        doc = json.loads(request.body)
        self.seen[doc["name"]] = doc
"""
    findings = lint_src(tmp_path, src,
                        select=["unbounded-cardinality"])
    assert rule_ids(findings) == ["unbounded-cardinality"]
    assert "http" in findings[0].message
    bounded = src.replace("self.seen = {}",
                          "self.seen = BoundedDict(256)")
    assert lint_src(tmp_path, bounded,
                    select=["unbounded-cardinality"]) == []


_DESER_BAD = """\
import pickle


class Proto:
    def handle(self, kind, payload):
        return pickle.loads(payload)
"""

_DESER_GOOD = """\
import hmac
import pickle


class Proto:
    def handle(self, kind, payload, tag):
        if not hmac.compare_digest(self._sign(payload), tag):
            raise ValueError("bad tag")
        return pickle.loads(payload)
"""


def test_unsafe_deserialize_fires_without_hmac(tmp_path):
    findings = lint_src(tmp_path, _DESER_BAD,
                        select=["unsafe-deserialize"])
    assert rule_ids(findings) == ["unsafe-deserialize"]
    assert lint_src(tmp_path, _DESER_GOOD,
                    select=["unsafe-deserialize"]) == []
    pragmad = _DESER_BAD.replace(
        "return pickle.loads(payload)",
        "return pickle.loads(payload)  "
        "# zlint: disable=unsafe-deserialize (test fixture)")
    assert lint_src(tmp_path, pragmad,
                    select=["unsafe-deserialize"]) == []


_PATH_BAD = """\
class Store:
    def handle(self, kind, payload):
        with open(payload["path"]) as f:
            return f.read()
"""

_PATH_GOOD = """\
class Store:
    def handle(self, kind, payload):
        with open(self._resolve_path(payload["path"])) as f:
            return f.read()

    def _resolve_path(self, name):
        return self.root + "/" + name.rsplit("/", 1)[-1]
"""


def test_untrusted_path_fires_on_wire_filename(tmp_path):
    findings = lint_src(tmp_path, _PATH_BAD,
                        select=["untrusted-path"])
    assert rule_ids(findings) == ["untrusted-path"]
    assert lint_src(tmp_path, _PATH_GOOD,
                    select=["untrusted-path"]) == []
    pragmad = _PATH_BAD.replace(
        'with open(payload["path"]) as f:',
        'with open(payload["path"]) as f:  '
        '# zlint: disable=untrusted-path (test fixture)')
    assert lint_src(tmp_path, pragmad,
                    select=["untrusted-path"]) == []


def test_sanitizer_annotation_kills_taint(tmp_path):
    """The ``# zlint: sanitizer`` recipe: a bounded tenant-table
    lookup that is NOT sanitizer-named still cleans what flows
    through it — the sanitizer-kills-taint pin."""
    src = """\
import numpy


def bounded_dims(doc):  # zlint: sanitizer (schema-checked upstream)
    return doc["rows"], doc["cols"]


class Proto:
    def handle(self, kind, payload):
        self.buf = numpy.zeros(bounded_dims(payload))
"""
    assert lint_src(tmp_path, src,
                    select=["untrusted-geometry"]) == []
    # the same flow WITHOUT the annotation fires — the pin is
    # falsifiable
    unannotated = src.replace(
        "  # zlint: sanitizer (schema-checked upstream)", "")
    findings = lint_src(tmp_path, unannotated,
                        select=["untrusted-geometry"])
    assert rule_ids(findings) == ["untrusted-geometry"]
    # the engine's bounded-lookup shape needs no annotation at all:
    # .get() off an untainted module table returns the TABLE's data
    table = """\
import numpy

TABLE = {"small": (4, 4), "big": (64, 64)}


class Proto:
    def handle(self, kind, payload):
        self.buf = numpy.zeros(TABLE.get(payload["profile"],
                                         (4, 4)))
"""
    assert lint_src(tmp_path, table,
                    select=["untrusted-geometry"]) == []


def test_range_guard_kills_taint(tmp_path):
    """An explicit comparison guard is a sanitizer: after the
    programmer bounded the value, downstream sinks stay quiet."""
    src = """\
import numpy


class Proto:
    def handle(self, kind, payload):
        n = payload["n"]
        if n > 4096:
            raise ValueError("too big")
        self.buf = numpy.zeros(n)
"""
    assert lint_src(tmp_path, src,
                    select=["untrusted-geometry"]) == []


# -- incremental analysis cache (ISSUE 20) -----------------------------


def _write_tree(tmp_path, files):
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(src)


def _run_cached(tmp_path, cache_dir):
    from veles.analysis.cache import AnalysisCache
    stats = []
    findings = analyze_paths([str(tmp_path / "pkg")],
                             base=str(tmp_path),
                             cache=AnalysisCache(str(cache_dir)),
                             stats=stats)
    return findings, {row["rule"]: row for row in stats}


def test_cache_reuses_and_reanalyzes_only_dependents(tmp_path):
    """THE cache-correctness pin: a warm run is all-cached with
    byte-identical findings; editing one module re-analyzes only the
    modules whose import closure contains it — and the findings
    still match an uncached run byte for byte."""
    _write_tree(tmp_path, {
        "pkg/a.py": "import os\n\nfrom pkg import b\n\n\n"
                    "def use():\n    return b.helper()\n",
        "pkg/b.py": "def helper():\n    return 1\n",
        "pkg/c.py": "X = 1\n",
    })
    cache_dir = tmp_path / "zc"
    cold, stats_cold = _run_cached(tmp_path, cache_dir)
    assert stats_cold["unused-import"]["fresh_modules"] == 3
    # the planted finding: a.py's unused os import
    assert [f.rule for f in cold] == ["unused-import"]
    warm, stats_warm = _run_cached(tmp_path, cache_dir)
    assert stats_warm["unused-import"]["fresh_modules"] == 0
    assert stats_warm["unused-import"]["cached_modules"] == 3
    assert json.dumps([f.as_dict() for f in warm]) \
        == json.dumps([f.as_dict() for f in cold])
    # edit b.py: a.py (imports b) and b.py re-analyze, c.py answers
    # from cache; a project-scope rule re-runs over everything
    (tmp_path / "pkg/b.py").write_text(
        "def helper():\n    return 2\n")
    edited, stats_edit = _run_cached(tmp_path, cache_dir)
    assert stats_edit["unused-import"]["fresh_modules"] == 2
    assert stats_edit["unused-import"]["cached_modules"] == 1
    assert stats_edit["untrusted-geometry"]["fresh_modules"] == 3
    uncached = analyze_paths([str(tmp_path / "pkg")],
                             base=str(tmp_path))
    assert json.dumps([f.as_dict() for f in edited]) \
        == json.dumps([f.as_dict() for f in uncached])


def test_cache_invalidates_on_import_graph_change(tmp_path):
    """Adding an import EDGE re-keys the importer: before the edge,
    editing b leaves a cached; after a.py gains ``import b``, an edit
    to b.py alone re-analyzes a.py too."""
    _write_tree(tmp_path, {
        "pkg/a.py": "def use():\n    return 1\n",
        "pkg/b.py": "def helper():\n    return 1\n",
    })
    cache_dir = tmp_path / "zc"
    _run_cached(tmp_path, cache_dir)
    (tmp_path / "pkg/b.py").write_text(
        "def helper():\n    return 2\n")
    _, stats = _run_cached(tmp_path, cache_dir)
    # no edge yet: only b itself re-analyzes
    assert stats["unused-import"]["fresh_modules"] == 1
    (tmp_path / "pkg/a.py").write_text(
        "from pkg import b\n\n\ndef use():\n    return b.helper()\n")
    _run_cached(tmp_path, cache_dir)            # warm the new graph
    (tmp_path / "pkg/b.py").write_text(
        "def helper():\n    return 3\n")
    _, stats = _run_cached(tmp_path, cache_dir)
    # the edge exists: b's edit invalidates a's closure key as well
    assert stats["unused-import"]["fresh_modules"] == 2


def test_cache_pragma_edit_rekeys_the_module(tmp_path):
    """Findings are stored post-pragma-filter — sound only because a
    pragma edit changes the module's content hash and therefore its
    key."""
    _write_tree(tmp_path, {"pkg/a.py": "import os\n"})
    cache_dir = tmp_path / "zc"
    cold, _ = _run_cached(tmp_path, cache_dir)
    assert [f.rule for f in cold] == ["unused-import"]
    (tmp_path / "pkg/a.py").write_text(
        "import os  # zlint: disable=unused-import (test)\n")
    warm, _ = _run_cached(tmp_path, cache_dir)
    assert warm == []


def test_cli_cache_and_stats(tmp_path, capsys):
    """--cache + --stats: the text table reports fresh/cached module
    counts, --json wraps {findings, stats}, and a warm --format json
    run (no --stats) is byte-identical to the cold one."""
    p = tmp_path / "m.py"
    p.write_text("import os\n\ntry:\n    pass\nexcept:\n    pass\n")
    cache_dir = str(tmp_path / "zc")
    rc = lint_main([str(p), "--cache", cache_dir, "--stats"])
    out_cold = capsys.readouterr().out
    assert rc == 1
    assert "fresh" in out_cold and "cached" in out_cold
    rc = lint_main([str(p), "--cache", cache_dir, "--stats",
                    "--format", "json"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert {f["rule"] for f in doc["findings"]} \
        == {"unused-import", "bare-except"}
    by_rule = {r["rule"]: r for r in doc["stats"]}
    assert by_rule["bare-except"]["cached_modules"] == 1
    assert by_rule["bare-except"]["fresh_modules"] == 0
    # cold vs warm byte-identity of the findings document
    lint_main([str(p), "--format", "json"])
    plain = capsys.readouterr().out
    lint_main([str(p), "--cache", cache_dir, "--format", "json"])
    warm = capsys.readouterr().out
    assert warm == plain


def test_cli_precommit_invocation(tmp_path, capsys):
    """The documented pre-commit hook line: ``velescli lint
    --changed-only --cache .zlint-cache --format sarif``. With a
    cache the full tree is kept (cross-file context intact) and the
    SARIF document is byte-identical to an uncached full run."""
    _git(tmp_path, "init", "-q")
    a = tmp_path / "a.py"
    a.write_text("X = 1\n")
    b = tmp_path / "b.py"
    b.write_text("Y = 2\n")
    _git(tmp_path, "add", ".")
    _git(tmp_path, "commit", "-qm", "seed")
    b.write_text("try:\n    pass\nexcept:\n    pass\n")
    cwd = os.getcwd()
    os.chdir(tmp_path)
    try:
        rc = lint_main([str(tmp_path), "--changed-only", "--cache",
                        str(tmp_path / ".zlint-cache"), "--format",
                        "sarif", "--select", "bare-except"])
        sarif_warm = capsys.readouterr().out
        rc_full = lint_main([str(tmp_path), "--format", "sarif",
                             "--select", "bare-except"])
        sarif_full = capsys.readouterr().out
    finally:
        os.chdir(cwd)
    assert rc == 1 and rc_full == 1
    assert sarif_warm == sarif_full
    doc = json.loads(sarif_warm)
    assert doc["runs"][0]["results"][0]["ruleId"] == "bare-except"


# -- the permanent gate ------------------------------------------------


def test_repo_wide_zero_findings_gate():
    """THE gate: the whole veles package stays at zero findings, the
    four taint rules included.

    If this fails, `velescli lint veles` reproduces it locally with
    file:line + a fix hint per finding. Fix the code, or — for a
    documented false positive / deliberate design — add
    `# zlint: disable=RULE (reason)` on the flagged line."""
    import veles
    pkg = os.path.dirname(os.path.abspath(veles.__file__))
    findings = analyze_paths([pkg], base=os.path.dirname(pkg))
    assert findings == [], (
        "zlint found %d violation(s) in veles/:\n%s"
        % (len(findings), "\n".join(f.render() for f in findings)))


def test_gate_would_catch_a_regression(tmp_path):
    """The gate is falsifiable: a rule violation planted in a copy of
    a real module shape IS caught (guards against the analyzer
    silently skipping the package)."""
    src = """\
import threading


class Worker(Unit):
    def __init__(self):
        self._lock = threading.Lock()

    def run(self):
        self.epoch = getattr(self, "epoch", 0) + 1
"""
    findings = lint_src(tmp_path, src, select=["checkpoint-state"])
    assert findings, "planted violation must be caught"


if __name__ == "__main__":
    pytest.main([__file__, "-v"])
