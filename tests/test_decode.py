"""Generative serving (ISSUE 11): paged KV cache, continuous
batching, streaming decode over the reactor.

The exactness anchor: the spec-walking serving decode must produce
token-for-token what the unit-walking offline ``generate()`` produces
— both paths ride the same shared math
(``dense_attention_core_fwd``/``block_fwd``/``attn_decode``), so a
drift here means the decode plane re-invented a formula.

HTTP coverage (satellite): a chunked ``/v1/generate`` response read
token by token over a REAL socket with the first chunk arriving while
the decode batch is still in flight, a client disconnect mid-stream
freeing its KV slot and counting
``veles_serving_rejected_total{reason="disconnect"}``, and probe
endpoints answering fast while decoding.
"""

import json
import os
import socket
import time
import urllib.error
import urllib.request

import numpy
import pytest

import veles.prng as prng
from veles.config import root

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- shared artifact ---------------------------------------------------


def _export_lm(base, name, stacked=False):
    """Initialize (untrained — decode prices machinery, not model
    quality) + export a tiny LM; returns (workflow, archive_dir)."""
    prng.seed_all(4242)
    from veles.znicz_tpu.models import transformer_lm
    saved_loader = root.lm.loader.to_dict()
    saved_model = root.lm.model.to_dict()
    root.lm.loader.update({"minibatch_size": 8, "n_train": 64,
                           "n_valid": 16, "seq_len": 16, "vocab": 8,
                           "max_period": 4})
    root.lm.model.update({"dim": 16, "heads": 2, "layers": 2,
                          "ffn_hidden": 32, "moe_experts": 0,
                          "attn_block": None, "attn_impl": None,
                          "stacked": stacked})
    try:
        wf = transformer_lm.create_workflow(name=name)
        wf.initialize(device="numpy")
        archive = str(base / ("archive_stacked" if stacked
                              else "archive"))
        wf.export_inference(archive)
        return wf, archive
    finally:
        root.lm.loader.update(saved_loader)
        root.lm.model.update(saved_model)


@pytest.fixture(scope="module")
def lm_env(tmp_path_factory):
    """One tiny LM archive + its live workflow (the offline-generate
    oracle) + a shared registry whose decode plane every HTTP test
    reuses (compiled programs are the expensive part)."""
    from veles.serving import ModelRegistry
    base = tmp_path_factory.mktemp("decode")
    wf, archive = _export_lm(base, "DecodeLM")
    registry = ModelRegistry(backend="numpy", decode_slots=4,
                             decode_max_len=256, decode_max_queue=2)
    registry.load("lm", archive)
    yield {"wf": wf, "archive": archive, "registry": registry,
           "base": base}
    registry.close()


def _offline(wf, prompt, n):
    from veles.znicz_tpu.generate import generate
    return generate(wf, numpy.asarray([prompt], numpy.int32), n,
                    temperature=0.0)[0].tolist()


# -- plan + engine -----------------------------------------------------


def test_plan_probe_and_rejection(lm_env, tmp_path):
    """Only causal-LM archives build a decode plan; a classifier
    archive is rejected loudly (and probe() says so quietly)."""
    from veles.serving import ArchiveModel, DecodePlan
    model = lm_env["registry"].get("lm").model
    assert DecodePlan.probe(model)
    plan = DecodePlan.from_archive(model)
    assert plan.n_caches == 2 and plan.vocab == 8
    # hand-written non-generative archive: a lone dense layer
    numpy.save(tmp_path / "fc_weights.npy",
               numpy.zeros((4, 4), numpy.float32))
    (tmp_path / "contents.json").write_text(json.dumps({
        "format": 1, "workflow": "clf", "input_sample_shape": [4],
        "units": [{"type": "all2all", "name": "fc",
                   "config": {"neurons": 4,
                              "output_sample_shape": [4]},
                   "weights": "fc_weights.npy", "bias": None}]}))
    clf = ArchiveModel.from_dir(str(tmp_path))
    assert not DecodePlan.probe(clf)
    with pytest.raises(ValueError, match="embedding"):
        DecodePlan.from_archive(clf)


def test_decode_matches_offline_generate(lm_env):
    """Greedy continuous decode == the unit-walking generate(),
    token for token — including two concurrent sequences of
    DIFFERENT lengths sharing the decode batch; sampled decode stays
    inside the vocabulary."""
    registry, wf = lm_env["registry"], lm_env["wf"]
    decoder = registry.decoder("lm")
    assert decoder is registry.decoder("lm")      # built once
    toks = decoder.generate([1, 2, 3, 1, 2, 3], max_tokens=8)
    assert toks == _offline(wf, [1, 2, 3, 1, 2, 3], 8)
    h1 = decoder.submit([1, 2, 3, 4, 5], max_tokens=12)
    h2 = decoder.submit([5, 6, 5], max_tokens=6)
    assert h1.wait(120) == _offline(wf, [1, 2, 3, 4, 5], 12)
    assert h2.wait(120) == _offline(wf, [5, 6, 5], 6)
    assert h1.finish_reason == h2.finish_reason == "length"
    assert decoder.engine.pool.in_use == 0        # slots recycled
    sampled = decoder.generate([1, 2, 3], max_tokens=8,
                               temperature=1.0)
    assert len(sampled) == 8
    assert all(0 <= t < 8 for t in sampled)


def test_decode_stacked_archive(lm_env):
    """The fused transformer_stack archive decodes through
    block_fwd/block_decode and matches the offline oracle too."""
    from veles.serving import (ArchiveModel, ContinuousBatcher,
                               GenerativeEngine)
    wf, archive = _export_lm(lm_env["base"], "DecodeStackLM",
                             stacked=True)
    engine = GenerativeEngine(ArchiveModel.from_dir(archive),
                              n_slots=2, max_len=32)
    batcher = ContinuousBatcher(engine, model="stack")
    try:
        toks = batcher.generate([1, 2, 1, 2, 1], max_tokens=6)
        assert toks == _offline(wf, [1, 2, 1, 2, 1], 6)
    finally:
        batcher.close()


def test_midflight_admission_eos_and_sharing(lm_env):
    """A request submitted while another decodes joins the IN-FLIGHT
    batch (shared steps, not appended ones), and an EOS hit frees its
    slot mid-flight without disturbing its neighbour."""
    registry, wf = lm_env["registry"], lm_env["wf"]
    decoder = registry.decoder("lm")
    steps0 = int(decoder._c_steps.get().value)
    long = decoder.submit([1, 2, 3, 4], max_tokens=60)
    # wait until the long request is genuinely decoding
    deadline = time.time() + 30
    while time.time() < deadline and len(long.tokens) < 3:
        time.sleep(0.005)
    assert len(long.tokens) >= 3
    want_short = _offline(wf, [5, 6, 5, 6], 30)
    # eos = the short request's own 3rd token -> it must stop there
    eos = want_short[2]
    short = decoder.submit([5, 6, 5, 6], max_tokens=30, eos=eos)
    got_short = short.wait(120)
    assert short.finish_reason == "eos"
    assert got_short == want_short[:got_short.index(eos) + 1]
    assert got_short[-1] == eos and len(got_short) <= 3
    got_long = long.wait(120)
    assert got_long == _offline(wf, [1, 2, 3, 4], 60)
    # sharing: the joined window advanced BOTH sequences per step, so
    # total steps stayed well under the sum of solo decodes
    steps = int(decoder._c_steps.get().value) - steps0
    assert steps < 60 + len(got_short)
    assert decoder.engine.pool.in_use == 0


def test_decode_shedding_and_validation(lm_env):
    """Admission is bounded: with every KV slot busy and the queue at
    max_queue, the next submit sheds (QueueFull -> the frontend's
    503); geometry violations are client errors before any slot is
    touched."""
    from veles.serving import QueueFull
    registry = lm_env["registry"]
    decoder = registry.decoder("lm")
    with pytest.raises(ValueError, match="max_len|KV slot"):
        decoder.submit([1] * 8, max_tokens=1000)
    with pytest.raises(ValueError):
        decoder.submit([], max_tokens=4)
    # non-finite client numbers are admission errors, not wedged
    # deadlines (NaN never compares expired) or OverflowError 500s
    with pytest.raises(ValueError, match="timeout_ms"):
        decoder.submit([1], timeout_ms=float("nan"))
    with pytest.raises(ValueError, match="timeout_ms"):
        decoder.submit([1], timeout_ms=float("inf"))
    with pytest.raises(ValueError, match="timeout_ms"):
        decoder.submit([1], timeout_ms=-5)
    with pytest.raises(ValueError, match="max_tokens"):
        decoder.submit([1], max_tokens=float("inf"))
    held = []
    try:
        for _ in range(4):                        # fill the 4 slots,
            h = decoder.submit([1, 2, 3], max_tokens=250)
            held.append(h)                        # waiting for each
            deadline = time.time() + 30           # admission so the
            while time.time() < deadline and not h.tokens:
                time.sleep(0.005)                 # queue stays empty
            assert h.tokens
        with pytest.raises(QueueFull):
            for _ in range(4):                    # queue cap is 2
                held.append(decoder.submit([1, 2], max_tokens=250))
        assert int(decoder._c_shed.get().value) >= 1
    finally:
        for h in held:
            h.cancel("test cleanup")
        for h in held:
            h.wait(120)
    deadline = time.time() + 10
    while time.time() < deadline and decoder.engine.pool.in_use:
        time.sleep(0.01)
    assert decoder.engine.pool.in_use == 0


def test_queued_request_expires_while_pool_saturated(lm_env):
    """Review regression: a queued request whose deadline passes
    while every KV slot is busy must expire (504) at the next step
    boundary — dead entries must not pin the bounded queue while
    long generations hold the pool."""
    from veles.serving import DeadlineExceeded
    decoder = lm_env["registry"].decoder("lm")
    held = []
    try:
        for _ in range(4):                        # saturate the pool
            h = decoder.submit([1, 2, 3], max_tokens=250)
            held.append(h)
            deadline = time.time() + 30
            while time.time() < deadline and not h.tokens:
                time.sleep(0.005)
        doomed = decoder.submit([1, 2], max_tokens=5, timeout_ms=40)
        with pytest.raises(DeadlineExceeded):
            doomed.wait(15)
        # it expired while the pool was STILL saturated
        assert decoder.engine.pool.in_use == 4
        assert int(decoder._c_expired.get().value) >= 1
    finally:
        for h in held:
            h.cancel("test cleanup")
        for h in held:
            h.wait(120)


def test_reload_and_unload_close_decode_plane(lm_env):
    """Review regression: an architecture-changing hot reload (and an
    unload) must close the OLD decode plane — worker stopped, KV pool
    released — instead of leaking it alongside the replacement."""
    from veles.serving import ModelRegistry
    _, stacked = _export_lm(lm_env["base"], "ReloadStackLM",
                            stacked=True)
    reg = ModelRegistry(backend="numpy", decode_slots=2,
                        decode_max_len=32)
    try:
        reg.load("m", lm_env["archive"])
        dec = reg.decoder("m")
        assert dec._running
        reg.load("m", stacked)          # different signature()
        assert not dec._running         # old plane closed
        with pytest.raises(RuntimeError, match="closed"):
            dec.submit([1], max_tokens=1)
        dec2 = reg.decoder("m")
        assert dec2 is not dec and dec2._running
        reg.unload("m")
        assert not dec2._running
        with pytest.raises(KeyError):
            reg.decoder("m")
    finally:
        reg.close()


def test_kv_pool_accounting(lm_env):
    """ISSUE 11 memory accounting: building the decode plane grows
    the entry's forward-cache estimate by exactly the preallocated
    KV pool bytes, and the pool gauges land on /metrics."""
    from veles import telemetry
    from veles.serving import ModelRegistry
    reg = ModelRegistry(backend="numpy", decode_slots=2,
                        decode_max_len=32)
    try:
        entry = reg.load("m", lm_env["archive"])
        assert entry.describe()["generative"] is True
        before = entry.cache_bytes()
        decoder = reg.decoder("m")
        pool = decoder.engine.pool
        # 2 layers x (K+V) x slots x heads x max_len x dh x 4B
        assert pool.nbytes() == 2 * 2 * 2 * 2 * 32 * 8 * 4
        assert entry.cache_bytes() == before + pool.nbytes()
        assert entry.describe()["decode"]["kv_pool_slots"] == 2
        text = telemetry.get_registry().render_prometheus()
        assert 'veles_serving_kv_pool_slots{model="m"} 2' in text
        assert reg.metrics()["m"]["decode"]["kv_pool_slots"] == 2
    finally:
        reg.close()


# -- HTTP: streaming over the reactor ----------------------------------


@pytest.fixture
def front(lm_env):
    from veles.serving.frontend import ServingFrontend
    f = ServingFrontend(lm_env["registry"], port=0)
    yield f
    f.close()


def _stream_generate(port, doc, stop_after=None, timeout=60):
    """POST /v1/generate over a raw socket; -> (headers, list of
    (arrival_time, parsed_line)). ``stop_after=N`` closes the socket
    after N token lines (the disconnecting client)."""
    s = socket.create_connection(("127.0.0.1", port), timeout=timeout)
    body = json.dumps(doc).encode()
    s.sendall(b"POST /v1/generate HTTP/1.1\r\nHost: t\r\n"
              b"Content-Type: application/json\r\n"
              b"Content-Length: %d\r\n\r\n%s" % (len(body), body))
    buf = b""
    while b"\r\n\r\n" not in buf:
        buf += s.recv(4096)
    head, _, buf = buf.partition(b"\r\n\r\n")
    lines = []          # (arrival wall time, parsed json line)
    chunks = b""
    done = False
    while not done:
        # parse complete chunks out of buf
        progressed = True
        while progressed:
            progressed = False
            if b"\r\n" in buf:
                size_s, _, rest = buf.partition(b"\r\n")
                try:
                    n = int(size_s, 16)
                except ValueError:
                    raise AssertionError("bad chunk size %r" % size_s)
                if n == 0:
                    done = True
                    break
                if len(rest) >= n + 2:
                    chunks += rest[:n]
                    buf = rest[n + 2:]
                    progressed = True
        now = time.perf_counter()
        while b"\n" in chunks:
            line, _, chunks = chunks.partition(b"\n")
            lines.append((now, json.loads(line)))
        n_tokens = sum(1 for _, d in lines if "token" in d)
        if stop_after is not None and n_tokens >= stop_after:
            s.close()
            return head.decode("latin-1"), lines
        if done:
            break
        data = s.recv(4096)
        if not data:
            break
        buf += data
    s.close()
    return head.decode("latin-1"), lines


def test_http_generate_streams_incrementally(lm_env, front):
    """THE acceptance path: >=16 tokens arrive as separate chunked
    reads over a real socket, and the FIRST token is read while the
    decode batch is still in flight (the server-side slot is still
    occupied when the client holds token #1)."""
    registry, wf = lm_env["registry"], lm_env["wf"]
    decoder = registry.decoder("lm")
    port = front.port
    s = socket.create_connection(("127.0.0.1", port), timeout=60)
    body = json.dumps({"model": "lm", "prompt": [1, 2, 3],
                       "max_tokens": 200}).encode()
    s.sendall(b"POST /v1/generate HTTP/1.1\r\nHost: t\r\n"
              b"Content-Type: application/json\r\n"
              b"Content-Length: %d\r\n\r\n%s" % (len(body), body))
    buf = b""
    while b'"token"' not in buf:
        buf += s.recv(4096)
    # first token is in hand — the sequence must still be decoding
    mid_flight = decoder.engine.pool.in_use
    t_first = time.perf_counter()
    reads = 1
    while b"0\r\n\r\n" not in buf:
        data = s.recv(4096)
        if not data:
            break
        reads += 1
        buf += data
    t_last = time.perf_counter()
    s.close()
    head, _, rest = buf.partition(b"\r\n\r\n")
    assert b"Transfer-Encoding: chunked" in head
    # re-assemble the chunked body and check the token ledger
    payload = b""
    while rest:
        size_s, _, rest = rest.partition(b"\r\n")
        n = int(size_s, 16)
        if n == 0:
            break
        payload += rest[:n]
        rest = rest[n + 2:]
    docs = [json.loads(l)
            for l in payload.decode().strip().split("\n")]
    toks = [d["token"] for d in docs if "token" in d]
    final = docs[-1]
    assert final["done"] and final["tokens"] == toks
    assert len(toks) == 200 >= 16
    assert toks == _offline(wf, [1, 2, 3], 200)
    # incrementality, two independent witnesses: the slot was still
    # occupied when token #1 was read, and the tail arrived across
    # many separate socket reads spread over real time
    assert mid_flight >= 1 or t_last - t_first > 0.01
    assert reads > 4


def test_http_generate_disconnect_frees_slot(lm_env, front):
    """Satellite: a client dropping mid-stream frees its KV slot at
    the next step boundary and counts a
    veles_serving_rejected_total{reason="disconnect"}."""
    from veles import telemetry
    registry = lm_env["registry"]
    decoder = registry.decoder("lm")
    head, lines = _stream_generate(
        front.port, {"model": "lm", "prompt": [1, 2],
                     "max_tokens": 250}, stop_after=2)
    assert "200" in head.split("\r\n")[0]
    deadline = time.time() + 15
    while time.time() < deadline and decoder.engine.pool.in_use:
        time.sleep(0.02)
    assert decoder.engine.pool.in_use == 0
    assert telemetry.get_registry().counter_total(
        "veles_serving_rejected_total", reason="disconnect") >= 1
    # the abandoned generation was cancelled, not run to completion
    assert decoder._c_finished.get().labels(
        "lm", "disconnect").value >= 1


def test_http_generate_nonstream_and_errors(lm_env, front, tmp_path):
    """stream:false answers once with the same greedy tokens; error
    paths: 404 unknown model, 400 non-generative archive, 400 bad
    geometry, 400 bad json."""
    wf = lm_env["wf"]
    base = "http://127.0.0.1:%d" % front.port

    def post(doc, raw=None):
        req = urllib.request.Request(
            base + "/v1/generate",
            raw if raw is not None else json.dumps(doc).encode(),
            method="POST",
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=120) as resp:
            return resp.status, json.loads(resp.read())

    code, doc = post({"model": "lm", "prompt": [1, 2, 3],
                      "max_tokens": 12, "stream": False})
    assert code == 200
    assert doc["tokens"] == _offline(wf, [1, 2, 3], 12)
    assert doc["finish_reason"] == "length" and doc["n"] == 12
    with pytest.raises(urllib.error.HTTPError) as err:
        post({"model": "nope", "prompt": [1], "stream": False})
    assert err.value.code == 404
    with pytest.raises(urllib.error.HTTPError) as err:
        post({"model": "lm", "prompt": [1], "max_tokens": 5000,
              "stream": False})
    assert err.value.code == 400
    with pytest.raises(urllib.error.HTTPError) as err:
        post(None, raw=b"{not json")
    assert err.value.code == 400
    # JSON carries bare NaN/Infinity and Python's parser accepts
    # them: a NaN timeout would mint a deadline that never expires
    # and an Infinity budget would OverflowError into a 500 — both
    # must be refused as client errors (admission hardening)
    with pytest.raises(urllib.error.HTTPError) as err:
        post(None, raw=b'{"model": "lm", "prompt": [1], '
             b'"timeout_ms": NaN, "stream": false}')
    assert err.value.code == 400
    assert "timeout_ms" in json.loads(err.value.read())["error"]
    with pytest.raises(urllib.error.HTTPError) as err:
        post(None, raw=b'{"model": "lm", "prompt": [1], '
             b'"max_tokens": Infinity, "stream": false}')
    assert err.value.code == 400
    assert "max_tokens" in json.loads(err.value.read())["error"]
    # a loaded NON-generative model answers 400, not 500
    numpy.save(tmp_path / "fc_weights.npy",
               numpy.zeros((4, 4), numpy.float32))
    (tmp_path / "contents.json").write_text(json.dumps({
        "format": 1, "workflow": "clf", "input_sample_shape": [4],
        "units": [{"type": "all2all", "name": "fc",
                   "config": {"neurons": 4,
                              "output_sample_shape": [4]},
                   "weights": "fc_weights.npy", "bias": None}]}))
    lm_env["registry"].load("clf", str(tmp_path))
    try:
        with pytest.raises(urllib.error.HTTPError) as err:
            post({"model": "clf", "prompt": [1], "stream": False})
        assert err.value.code == 400
        assert "embedding" in json.loads(err.value.read())["error"]
    finally:
        lm_env["registry"].unload("clf")


def test_probes_fast_while_decode_in_flight(lm_env, front):
    """Satellite: /healthz and /readyz answer inline on the loop in
    well under 0.5s while a decode batch runs — and the readiness doc
    carries the serving:<port>:decode check."""
    registry = lm_env["registry"]
    decoder = registry.decoder("lm")
    handle = decoder.submit([1, 2, 3], max_tokens=250)
    base = "http://127.0.0.1:%d" % front.port
    try:
        worst = 0.0
        for _ in range(5):
            for path in ("/healthz", "/readyz"):
                t0 = time.perf_counter()
                with urllib.request.urlopen(base + path,
                                            timeout=10) as resp:
                    doc = json.loads(resp.read())
                worst = max(worst, time.perf_counter() - t0)
                if path == "/readyz":
                    assert "serving:%d:decode" % front.port \
                        in doc["checks"]
        assert worst < 0.5, worst
    finally:
        handle.cancel("test done")
        handle.wait(120)


def test_decode_readiness_flips_on_dead_worker(lm_env):
    """serving:<port>:decode goes not-ready when a model's decode
    worker dies (and names the model)."""
    from veles.serving.frontend import ServingFrontend
    f = ServingFrontend(lm_env["registry"], port=0)
    try:
        decoder = lm_env["registry"].decoder("lm")
        ok, why = f._check_decode()
        assert ok, why
        # simulate a crashed (not closed) worker
        was_running = decoder._running
        try:
            alive = decoder._thread
            decoder._thread = _DeadThread()
            ok, why = f._check_decode()
            assert not ok and "lm" in why
        finally:
            decoder._thread = alive
            decoder._running = was_running
        ok, _ = f._check_decode()
        assert ok
    finally:
        f.close()


class _DeadThread:
    @staticmethod
    def is_alive():
        return False
