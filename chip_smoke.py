"""chip_smoke.py — the quickest proof that the system still starts on
the chip.

    python chip_smoke.py            # one TPU chip, < 1200 s
    python chip_smoke.py --chips 4  # the four-chip host (DP, DPxTP, ring)

Drives the main path once through the entry points a user would call,
at the full width of the transformer-base LM (dim 768, 12 heads, 12
layers, FFN 3072, vocab 16384; random weights from ``--seed 1``):

1. ``train_short`` — ``python -m veles .../transformer_lm.py -d tpu``
   at S=512 (the one-tile Pallas kernels), two epochs, exporting the
   archive the server phase loads;
2. ``train_long`` — the same entry point at S=8192, batch 4 (Pallas
   forward + fused backward); its compiled step must hold Mosaic
   kernels (``tpu_custom_call`` in the optimized HLO);
3. ``kernels`` — every shipped Pallas kernel compiled for real
   (``interpret=False``) at the step-2 shapes against the dense float32
   reference, then a check of whether ``block_until_ready`` blocks;
4. ``serve`` — ``python velescli.py serve --backend jit`` on the
   step-1 archive: one ``/v1/predict``, four concurrent
   ``/v1/generate`` (one streamed over a raw socket), KV slots back to
   free, SIGTERM, exit 0.

THIS process never imports jax: a chip belongs to one process at a
time, so every phase is a child, run one after another, all sharing the
one compile cache ``veles.backends.enable_compile_cache`` places. Any
child's non-zero exit or any failed check ends the run non-zero right
there — nothing is retried and nothing continues on the CPU. On
success the LAST stdout line is one JSON object
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": n}}``.
"""

import argparse
import concurrent.futures
import functools
import json
import math
import os
import queue
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
WORKFLOW = os.path.join("veles", "znicz_tpu", "models",
                        "transformer_lm.py")

#: the whole run's wall budget, compilation included (the driver's
#: limit is 1200 s)
BUDGET_S = 1150.0

#: ONE table of sizes: every phase's command line and every kernel
#: shape is built from an entry here. "full" is what the chip runs
#: (the benchmark's lm110m configuration has these widths); "tiny" is
#: the same lines at toy widths, run on the CPU by
#: tests/test_chip_smoke.py so a typo is found there and not on chip
#: time.
SIZES = {
    "full": {
        "model": {"dim": 768, "heads": 12, "layers": 12,
                  "ffn_hidden": 3072, "attn_block": 256},
        "vocab": 16384,
        "train_short": {"seq_len": 512, "minibatch_size": 8,
                        "n_train": 64, "n_valid": 8},
        "train_long": {"seq_len": 8192, "minibatch_size": 4,
                       "n_train": 32, "n_valid": 4},
        # the sample's default 0.05 (momentum 0.9) overshoots on the
        # 32-sequence corpus at S=8192 — train loss 9.9, 10.4, 13.7 on
        # the chip, on the Pallas AND the scan path alike; 0.01 falls
        # 9.8, 7.5, 6.1 (PERF.md, PR 21)
        "train_long_lr": 0.01,
        # Pallas tile at the long shape (MultiHeadAttention's auto
        # choice: largest power-of-two divisor of S up to 512)
        "tile": 512,
        # the short-sequence kernels at train_short's S: the batch of
        # the benchmark's S=512 cells, one tile a (batch, head) row
        "kernels_short_batch": 32,
        # bias_grad shapes: AlexNet conv1 (N = 128*55*55, K = 96, the
        # sample's soft "relu") and the LM head (N = 4*8192, K = vocab)
        "bias_grad": [(128 * 55 * 55, 96, "relu"),
                      (4 * 8192, 16384, "linear")],
        "sync_matmul": (8192, 200),     # (n, chained matmuls)
        "serve": {"decode_slots": 8, "decode_max_len": 256,
                  "max_tokens": 32},
    },
    "tiny": {
        "model": {"dim": 32, "heads": 2, "layers": 2,
                  "ffn_hidden": 64, "attn_block": 16},
        "vocab": 32,
        "train_short": {"seq_len": 32, "minibatch_size": 8,
                        "n_train": 16, "n_valid": 8},
        "train_long": {"seq_len": 64, "minibatch_size": 4,
                       "n_train": 8, "n_valid": 4},
        "train_long_lr": 0.01,
        "tile": 32,
        "kernels_short_batch": 4,
        "bias_grad": [(700, 24, "relu"), (256, 160, "linear")],
        "sync_matmul": (128, 8),
        "serve": {"decode_slots": 4, "decode_max_len": 32,
                  "max_tokens": 8},
    },
}

#: ``--chips 4`` legs: root.lm.parallel overrides on the train_long
#: line, and the collectives the partitioned HLO must then contain
LEGS = (
    ("dp4", {"data": 4}, ["all-reduce"]),
    ("dp2_tp2", {"data": 2, "model": 2}, ["all-reduce"]),
    # ring attention, per-shard S = 2048 >= PALLAS_AUTO_MIN_S: Pallas
    # kernels inside shard_map
    ("ring4", {"seq": 4}, ["collective-permute"]),
)

#: the kernels phase's bounds on |kernel - dense float32 reference|:
#: the tier-1 tests' own, absolute (tests/test_pallas_attention.py,
#: tests/test_pallas_grads.py)
TOL_BF16 = 2e-2         # bf16 tensors (what the chip computes in)
TOL_F32 = 2e-4          # float32 tensors (the CPU rehearsal)
TOL_LSE = 1e-4          # the row statistics, which never narrow
#: the tests hold those bounds on O(1) outputs; at S=8192 a gradient
#: reaches 4..8, where storing the RESULT in bf16 (8 significant bits,
#: so up to 2^-8 of its magnitude) already costs 1.6e-2. A bf16 result
#: is allowed that much of the reference's magnitude, element by
#: element, on top of the tests' bound — ``allclose(atol, rtol)``.
RTOL_BF16_RESULT = 2.0 ** -8
#: bias_grad accumulates in float32 whatever the inputs: the tests
#: allow 2e-3 on column sums of magnitude ~2e2 (4096 rows), the same
#: 1e-5 of the largest column sum at the smoke's row counts
TOL_BIAS_GRAD_ABS, TOL_BIAS_GRAD_REL = 2e-3, 1e-5
#: heads per call of the dense reference, which holds several
#: (heads, S, S) float32 matrices at once
REF_HEADS = 2
#: every device of a multi-chip leg must hold at least this much
MIN_DEVICE_BYTES = 64 << 20


class SmokeFailure(Exception):
    """A phase failed or a check did not hold."""


def check(ok, what):
    if not ok:
        raise SmokeFailure(what)


# -- command lines (shared with tests/test_chip_smoke.py) ---------------


def train_argv(size, phase, device, result_file, export_dir=None,
               parallel=None):
    """argv of one training run, exactly what a shell would pass after
    ``python -m veles``."""
    cfg = SIZES[size]
    argv = [WORKFLOW, "-d", device, "--seed", "1", "--no-stats"]
    argv += ["root.lm.model.%s=%r" % kv
             for kv in sorted(cfg["model"].items())]
    loader = dict(cfg[phase], vocab=cfg["vocab"])
    argv += ["root.lm.loader.%s=%r" % kv for kv in sorted(loader.items())]
    argv += ["root.lm.parallel.%s=%r" % kv
             for kv in sorted((parallel or {}).items())]
    if phase + "_lr" in cfg:
        argv.append("root.lm.train.learning_rate=%r" % cfg[phase + "_lr"])
    argv += ["root.lm.decision.max_epochs=2",
             "--result-file", result_file]
    if export_dir:
        argv += ["--export-inference", export_dir]
    return argv


def serve_argv(size, archive):
    """argv of the server, after ``python velescli.py``."""
    serve = SIZES[size]["serve"]
    return ["serve", "--model", "lm=%s" % archive, "--backend", "jit",
            "--port", "0", "--decode-slots", str(serve["decode_slots"]),
            "--decode-max-len", str(serve["decode_max_len"])]


# -- the parent: process plumbing ---------------------------------------


class Runner:
    """Starts children in their own process groups, holds them to the
    run's deadline and kills whatever is still alive on the way out."""

    def __init__(self, budget_s, env=None):
        self.budget_s = budget_s
        self.deadline = time.monotonic() + budget_s
        self.env = dict(os.environ, **(env or {}))
        self.live = []

    def remaining(self):
        left = self.deadline - time.monotonic()
        check(left > 0, "out of time (%ds budget)" % self.budget_s)
        return left

    def start(self, cmd, **kwargs):
        print("+ %s" % " ".join(cmd), flush=True)
        proc = subprocess.Popen(cmd, cwd=HERE, env=self.env,
                                start_new_session=True, text=True,
                                **kwargs)
        self.live.append(proc)
        return proc

    def run(self, name, cmd):
        """Run one child to its end; -> its stdout. Non-zero exit or
        the deadline fails the run."""
        t0 = time.monotonic()
        proc = self.start(cmd, stdout=subprocess.PIPE)
        try:
            out, _ = proc.communicate(timeout=self.remaining())
        except subprocess.TimeoutExpired:
            raise SmokeFailure("phase %s ran past the deadline" % name)
        sys.stdout.write(out)
        check(proc.returncode == 0,
              "phase %s: child exited %s" % (name, proc.returncode))
        self.timed(name, t0)
        return out

    def timed(self, name, t0):
        print("phase %s: %.1fs" % (name, time.monotonic() - t0),
              flush=True)

    def close(self):
        for proc in self.live:
            if proc.poll() is None:
                try:
                    os.killpg(proc.pid, signal.SIGKILL)
                except ProcessLookupError:
                    pass
                proc.wait()


def last_json(text, what):
    """The JSON object a child printed as its last stdout line."""
    lines = [l for l in text.splitlines() if l.strip()]
    check(lines, "%s printed nothing" % what)
    try:
        return json.loads(lines[-1])
    except ValueError:
        raise SmokeFailure("%s: last line is not JSON: %r"
                           % (what, lines[-1][:200]))


def check_device(report, device, what):
    """Every child names the device it ran on; anything but the one
    asked for (``tpu`` on the chip) fails the smoke."""
    check(isinstance(report, dict) and report.get("platform") == device,
          "%s ran on %r, not on %r" % (what, report, device))
    print("%s device: %s" % (what, json.dumps(report)), flush=True)
    return report


def check_history(result_file, device, what):
    """Loss checks of one training run, from its --result-file: every
    loss finite, the last train loss below the first."""
    with open(result_file) as f:
        doc = json.load(f)
    check_device(doc.get("device"), device, what)
    history = doc["history"]
    losses = [h[cls]["loss"] for h in history
              for cls in ("validation", "train") if cls in h]
    check(len(history) >= 2 and losses and
          all(math.isfinite(v) for v in losses),
          "%s: losses not finite over >= 2 epochs: %r" % (what, history))
    first, last = history[0]["train"]["loss"], history[-1]["train"]["loss"]
    check(last < first, "%s: train loss did not fall (%r -> %r)"
          % (what, first, last))
    print("%s train loss: %.4f -> %.4f" % (what, first, last),
          flush=True)
    return doc["device"]


def child_cmd(name, size, device, *extra):
    return [sys.executable, os.path.join(HERE, "chip_smoke.py"),
            "--child", name, "--size", size, "--device", device,
            *extra]


# -- the parent: phases -------------------------------------------------


def phase_train_short(runner, size, device, workdir):
    result = os.path.join(workdir, "train_short.json")
    archive = os.path.join(workdir, "archive")
    runner.run("train_short",
               [sys.executable, "-m", "veles"] + train_argv(
                   size, "train_short", device, result, archive))
    check_history(result, device, "train_short")
    return archive


def phase_train_long(runner, size, device, workdir):
    out = runner.run("train_long", child_cmd(
        "train", size, device, "--workdir", workdir))
    report = last_json(out, "train_long")
    check_history(report["result_file"], device, "train_long")
    if device == "tpu":
        check(report["mosaic_kernels"] > 0,
              "train_long: no tpu_custom_call in the optimized HLO — "
              "the S=%d step holds no Pallas kernel"
              % SIZES[size]["train_long"]["seq_len"])
    print("train_long mosaic kernels in HLO: %d"
          % report["mosaic_kernels"], flush=True)


def phase_kernels(runner, size, device):
    out = runner.run("kernels", child_cmd("kernels", size, device))
    report = last_json(out, "kernels")
    check_device(report["device"], device, "kernels")
    return report


def phase_serve(runner, size, device, archive):
    cfg = SIZES[size]
    t0 = time.monotonic()
    proc = runner.start(
        [sys.executable, os.path.join(HERE, "velescli.py")]
        + serve_argv(size, archive), stdout=subprocess.PIPE)
    lines = queue.Queue()

    def pump():
        for line in proc.stdout:
            sys.stdout.write("serve| " + line)
            lines.put(line)
        lines.put(None)

    threading.Thread(target=pump, daemon=True).start()
    # the first stdout JSON line carries the bound address and the
    # device (it is printed after the bucket ladder has compiled)
    doc = None
    while doc is None:
        try:
            line = lines.get(timeout=runner.remaining())
        except queue.Empty:
            raise SmokeFailure("serve: no address line before the "
                               "deadline")
        check(line is not None, "serve: exited (%s) before printing "
              "its address" % proc.poll())
        if line.lstrip().startswith("{"):
            doc = json.loads(line)
    base = doc["serving"]
    check_device(doc.get("device"), device, "serve")
    wait_ready(base, runner)

    seq = cfg["train_short"]["seq_len"]
    code, reply = http_json(base + "/v1/models", None, runner)
    check(code == 200 and reply["models"][0]["platform"] == device
          and reply["models"][0]["generative"],
          "serve: /v1/models says %r" % (reply,))
    row = [(7 * i + 3) % cfg["vocab"] for i in range(seq)]
    code, reply = http_json(
        base + "/v1/predict",
        {"model": "lm", "inputs": [row], "timeout_ms": 120000}, runner)
    check(code == 200, "serve: /v1/predict -> %s %r"
          % (code, str(reply)[:300]))
    check_logits(reply["outputs"], seq, cfg["vocab"])

    max_tokens = cfg["serve"]["max_tokens"]

    def generate(i):
        req = {"model": "lm", "prompt": [1 + i, 2 + i, 3 + i],
               "max_tokens": max_tokens, "temperature": 0.0}
        if i == 0:      # streamed, read chunk by chunk off a raw socket
            return stream_generate(base, req, runner)
        code, reply = http_json(base + "/v1/generate",
                                dict(req, stream=False), runner)
        return code, reply.get("tokens")

    with concurrent.futures.ThreadPoolExecutor(4) as pool:
        futures = [pool.submit(generate, i) for i in range(4)]
        results = [f.result(timeout=runner.remaining())
                   for f in futures]
    for i, (code, tokens) in enumerate(results):
        check(code == 200 and isinstance(tokens, list)
              and len(tokens) == max_tokens
              and all(isinstance(t, int) and 0 <= t < cfg["vocab"]
                      for t in tokens),
              "serve: generate #%d -> %s %r" % (i, code, tokens))
    print("serve: 4 x %d tokens generated" % max_tokens, flush=True)

    slots = cfg["serve"]["decode_slots"]
    deadline = time.monotonic() + 15
    while True:
        code, text = http(base + "/metrics", None, runner)
        pool = metric_value(text, "veles_serving_kv_pool_slots")
        used = metric_value(text, "veles_serving_kv_slots_in_use")
        if (pool, used) == (slots, 0) or time.monotonic() > deadline:
            break
        time.sleep(0.1)
    check((pool, used) == (slots, 0),
          "serve: KV pool %r slots, %r in use after the requests "
          "(want %d, 0)" % (pool, used, slots))

    proc.send_signal(signal.SIGTERM)
    try:
        rc = proc.wait(timeout=min(60, runner.remaining()))
    except subprocess.TimeoutExpired:
        raise SmokeFailure("serve: still running 60s after SIGTERM")
    check(rc == 0, "serve: exit code %s after SIGTERM" % rc)
    runner.timed("serve", t0)


def check_logits(outputs, seq, vocab):
    """/v1/predict on one row: (1, seq, vocab) finite logits."""
    check(len(outputs) == 1 and len(outputs[0]) == seq
          and all(len(pos) == vocab for pos in outputs[0]),
          "serve: /v1/predict outputs are not (1, %d, %d)"
          % (seq, vocab))
    check(all(math.isfinite(v) for pos in outputs[0] for v in pos),
          "serve: /v1/predict returned non-finite logits")
    print("serve: /v1/predict -> (1, %d, %d) finite logits"
          % (seq, vocab), flush=True)


def http(url, doc, runner):
    """-> (status, body text); 4xx/5xx are statuses, not exceptions."""
    data = None if doc is None else json.dumps(doc).encode()
    req = urllib.request.Request(
        url, data=data, headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(
                req, timeout=runner.remaining()) as resp:
            return resp.status, resp.read().decode()
    except urllib.error.HTTPError as exc:
        return exc.code, exc.read().decode()


def http_json(url, doc, runner):
    code, text = http(url, doc, runner)
    return code, json.loads(text)


def wait_ready(base, runner):
    while True:
        try:
            code, _ = http(base + "/readyz", None, runner)
        except OSError:
            code = None
        if code == 200:
            return
        runner.remaining()
        time.sleep(0.2)


def metric_value(text, name):
    """Value of the first sample of a Prometheus family, or None."""
    for line in text.splitlines():
        if line.startswith(name + "{") or line.startswith(name + " "):
            return float(line.rsplit(" ", 1)[1])
    return None


def stream_generate(base, doc, runner):
    """POST /v1/generate with streaming on, read as chunked ndjson off
    a raw socket (urllib would buffer the whole response);
    -> (status, tokens). The per-token lines must add up to the
    terminal line's token list."""
    host, port = base.rsplit("/", 1)[1].rsplit(":", 1)
    body = json.dumps(dict(doc, stream=True)).encode()
    with socket.create_connection(
            (host, int(port)), timeout=runner.remaining()) as s:
        s.sendall(b"POST /v1/generate HTTP/1.1\r\nHost: smoke\r\n"
                  b"Content-Type: application/json\r\n"
                  b"Content-Length: %d\r\n\r\n%s" % (len(body), body))
        buf = b""
        # to the terminal chunk — or, for an error reply (not chunked),
        # just past the headers
        while not buf.endswith(b"0\r\n\r\n") and not (
                b"\r\n\r\n" in buf and b" 200 " not in buf[:16]):
            data = s.recv(65536)
            if not data:
                break
            buf += data
    head, _, rest = buf.partition(b"\r\n\r\n")
    status = int(head.split(b" ", 2)[1])
    if status != 200:
        return status, rest.decode("latin-1")[:300]
    check(b"chunked" in head, "serve: streamed reply is not chunked")
    payload = b""
    while rest:
        size, _, rest = rest.partition(b"\r\n")
        n = int(size, 16)
        if n == 0:
            break
        payload += rest[:n]
        rest = rest[n + 2:]
    docs = [json.loads(l) for l in payload.decode().splitlines() if l]
    tokens = [d["token"] for d in docs if "token" in d]
    check(docs and docs[-1].get("done") and docs[-1]["tokens"] == tokens,
          "serve: streamed lines do not add up: %r" % docs[-3:])
    return status, tokens


def run_single_chip(size="full", device="tpu", budget_s=BUDGET_S,
                    env=None):
    """The four phases, one after another; -> the device facts."""
    runner = Runner(budget_s, env)
    try:
        with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
            archive = phase_train_short(runner, size, device, tmp)
            phase_train_long(runner, size, device, tmp)
            kernels = phase_kernels(runner, size, device)
            phase_serve(runner, size, device, archive)
        return kernels["device"]
    finally:
        runner.close()


def run_four_chips(size="full", device="tpu", budget_s=3400.0, env=None):
    """DP=4, DP2xTP2 and ring SP=4 on the train_long line."""
    runner = Runner(budget_s, env)
    facts = None
    try:
        with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
            for leg, _, _ in LEGS:
                out = runner.run(leg, child_cmd(
                    "train", size, device, "--workdir", tmp,
                    "--leg", leg))
                report = last_json(out, leg)
                facts = check_history(report["result_file"], device, leg)
                check(facts["count"] >= 4,
                      "%s: %d device(s), need 4" % (leg, facts["count"]))
                check(report["param_devices"] == 4,
                      "%s: first parameter leaf lives on %d device(s), "
                      "not 4" % (leg, report["param_devices"]))
                print("%s collectives: %s" % (
                    leg, json.dumps(report["collectives"])), flush=True)
                if device == "tpu":
                    check(report["mosaic_kernels"] > 0,
                          "%s: no tpu_custom_call in the optimized HLO"
                          % leg)
                    used = report["bytes_in_use"]
                    check(len(used) >= 4 and all(
                        b is not None and b >= MIN_DEVICE_BYTES
                        for b in used[:4]),
                        "%s: device memory in use %r — not every chip "
                        "holds a share" % (leg, used))
                    print("%s bytes in use per device: %r; mosaic "
                          "kernels in HLO: %d" % (
                              leg, used, report["mosaic_kernels"]),
                          flush=True)
        return facts
    finally:
        runner.close()


# -- children (these import jax) ----------------------------------------


def child_train(size, device, workdir, leg):
    """One train_long run through ``veles.__main__.Main`` with the argv
    a shell would pass, then a look at the live workflow: Mosaic
    kernels in the optimized HLO, and for a multi-chip leg the
    collectives, the parameter placement and every device's memory."""
    sys.path.insert(0, HERE)
    os.chdir(HERE)
    from veles.__main__ import Main
    legs = {name: (spec, expect) for name, spec, expect in LEGS}
    spec, expect = legs.get(leg, ({}, []))
    result_file = os.path.join(workdir, "train_long_%s.json" % leg)
    main = Main(train_argv(size, "train_long", device, result_file,
                           parallel=spec))
    main.run()
    import jax
    from veles.znicz_tpu import parallel
    step = main.workflow.xla_step
    hlo = step.lowered_epoch_hlo(optimized=True)
    leaf = jax.tree_util.tree_leaves(step.params)[0]
    report = {
        "result_file": result_file,
        "mosaic_kernels": hlo.count("tpu_custom_call"),
        "collectives": parallel.assert_collectives(step, expect,
                                                   hlo=hlo),
        "param_devices": len(leaf.sharding.device_set),
        "bytes_in_use": [
            (d.memory_stats() or {}).get("bytes_in_use")
            for d in main.workflow.device.jax_devices],
    }
    print(json.dumps(report), flush=True)


def pallas_variants(size, interpret, dtype):
    """{name: (jitted function, argument specs)} — every Pallas kernel
    the repo ships, at the train_long shapes of ``size``, and the
    short-sequence kernels (one tile a row, what the auto rule runs
    at S=512) at train_short's S. ``fwd`` and ``bwd_fused`` are the
    K-loop kernels a step runs (score tile held (keys, queries), V
    handed over as (BH, dh, S), lse and dq leaving lane-dense). The
    kernels child runs them;
    tests/test_chip_smoke.py lowers the same table for the TPU
    without a chip."""
    import jax
    import jax.numpy as jnp
    from veles.znicz_tpu.ops import pallas_grads as PG
    from veles.znicz_tpu.parallel import pallas_attention as PA
    cfg = SIZES[size]
    b = cfg["train_long"]["minibatch_size"]
    h = cfg["model"]["heads"]
    s = cfg["train_long"]["seq_len"]
    dh = cfg["model"]["dim"] // h

    def shapes(b, s):
        t = jax.ShapeDtypeStruct((b, h, s, dh), dtype)
        row = jax.ShapeDtypeStruct((b, h, s), jnp.float32)
        return (t, t, t), (t, t, t, t, row, t)  # bwd: q k v out lse dout

    def attn(fn, tile=cfg["tile"]):
        return jax.jit(functools.partial(
            fn, causal=True, block_q=tile, block_k=tile,
            interpret=interpret))

    fwd, bwd = shapes(b, s)
    s_short = cfg["train_short"]["seq_len"]
    fwd_short, bwd_short = shapes(cfg["kernels_short_batch"], s_short)
    variants = {
        "fwd": (attn(PA.flash_attention_fwd), fwd),
        "bwd_fused": (attn(PA.flash_attention_bwd), bwd),
        "fwd_short": (attn(PA.flash_attention_fwd, tile=s_short),
                      fwd_short),
        "bwd_short": (attn(PA.flash_attention_bwd, tile=s_short),
                      bwd_short),
    }
    for n, k, act in cfg["bias_grad"]:
        g = jax.ShapeDtypeStruct((n, k), dtype)
        variants["bias_grad_%dx%d_%s" % (n, k, act)] = (
            jax.jit(functools.partial(PG.bias_grad, activation=act,
                                      interpret=interpret)), (g, g))
    return variants


def child_kernels(size, device):
    """Compile and run every shipped Pallas kernel for real at the
    train_long shapes, compare each with the dense float32 reference,
    then time how ``block_until_ready`` and a read-back wait."""
    sys.path.insert(0, HERE)
    import jax
    import jax.numpy as jnp
    import numpy
    from veles import backends
    from veles.znicz_tpu.ops import activations as A
    from veles.znicz_tpu.ops.attention import (
        dense_attention_core_bwd, dense_attention_core_fwd)

    backends.enable_compile_cache()
    facts = backends.device_report()
    print("kernels device: %s" % json.dumps(facts), flush=True)
    check(facts["platform"] == device,
          "kernels child is on %r, asked for %r"
          % (facts["platform"], device))
    # never decided from a failed query: the Mosaic compiler on the
    # chip, the interpreter only in the CPU rehearsal of this script
    interpret = device != "tpu"
    cd = jnp.bfloat16 if device == "tpu" else jnp.float32
    tol = TOL_BF16 if device == "tpu" else TOL_F32
    cfg = SIZES[size]
    variants = pallas_variants(size, interpret, cd)
    gen = numpy.random.Generator(numpy.random.PCG64(21))

    def rand(spec):
        return jnp.asarray(
            gen.standard_normal(spec.shape, numpy.float32), spec.dtype)

    rtol = RTOL_BF16_RESULT if cd == jnp.bfloat16 else 0.0
    hi = functools.partial(jnp.matmul, precision="highest")
    errors = {}

    def settle(name, err, net, atol):
        errors[name] = [err, net, atol]
        print("kernel %-32s max|err| %.3e; net of the result's own "
              "rounding %.3e (bound %.3e)" % (name, err, net, atol),
              flush=True)
        check(net <= atol,
              "kernel %s: error %.3e > %.3e" % (name, net, atol))

    def check_attention(fwd_name, bwd_name):
        """Run the forward ``fwd_name`` and, on its out and lse, the
        backward ``bwd_name`` on one random q, k, v, dout of their
        shape and hold every result to the dense float32 reference."""
        q, k, v, dout = (rand(variants[fwd_name][1][0])
                         for _ in range(4))
        b, h, s, dh = q.shape
        got, bounds = {}, {}    # "variant.tensor" -> result, (atol, rtol)
        out, lse = variants[fwd_name][0](q, k, v)
        got[fwd_name + ".out"], got[fwd_name + ".lse"] = out, lse
        bounds[fwd_name + ".out"] = (tol, rtol)
        bounds[fwd_name + ".lse"] = (TOL_LSE, 0.0)
        for gname, g in zip(
                ("dq", "dk", "dv"),
                variants[bwd_name][0](q, k, v, out, lse, dout)):
            got["%s.%s" % (bwd_name, gname)] = g
            bounds["%s.%s" % (bwd_name, gname)] = (tol, rtol)
        for name, g in got.items():
            check(g.shape == (q.shape[:3] if name.endswith(".lse")
                              else q.shape),
                  "kernel %s: shape %r" % (name, g.shape))
        scale = numpy.float32(1.0 / numpy.sqrt(dh))

        @jax.jit
        def slice_errors(q, k, v, dout, got):
            """Per result, on one (1, REF_HEADS, S, dh) slice, against
            the dense float32 reference: max|err|, and max(|err| -
            rtol*|ref|) — what the absolute bound is held against."""
            q32, k32, v32, do32 = (t.astype(jnp.float32)
                                   for t in (q, k, v, dout))
            probs, ctx = dense_attention_core_fwd(
                jnp, q32, k32, v32, True, scale, hi)
            scores = jnp.where(
                jnp.arange(s)[None, :] > jnp.arange(s)[:, None],
                -jnp.inf, hi(q32, k32.transpose(0, 1, 3, 2)) * scale)
            want = dict(zip(("dq", "dk", "dv"), dense_attention_core_bwd(
                jnp, q32, k32, v32, probs, do32, scale, hi)),
                out=ctx, lse=jax.nn.logsumexp(scores, axis=-1))
            errs = {}
            for name, g in got.items():
                ref = want[name.rsplit(".", 1)[1]]
                err = jnp.abs(g.astype(jnp.float32) - ref)
                errs[name] = jnp.stack([
                    err.max(),
                    (err - bounds[name][1] * jnp.abs(ref)).max()])
            return errs

        # EVERY batch and head: the reference is O(S^2) memory per
        # head, so it is taken a few heads at a time. A non-finite
        # result makes its error non-finite, which no bound admits.
        worst = {name: numpy.full(2, -numpy.inf) for name in got}
        for bi in range(b):
            for h0 in range(0, h, REF_HEADS):
                cut = (slice(bi, bi + 1), slice(h0, h0 + REF_HEADS))
                errs = jax.device_get(slice_errors(
                    q[cut], k[cut], v[cut], dout[cut],
                    {name: g[cut] for name, g in got.items()}))
                for name, pair in errs.items():     # NaN propagates
                    worst[name] = numpy.maximum(worst[name], pair)
        for name in got:
            settle(name, float(worst[name][0]), float(worst[name][1]),
                   bounds[name][0])

    check_attention("fwd", "bwd_fused")
    check_attention("fwd_short", "bwd_short")

    for n, kk, act in cfg["bias_grad"]:
        name = "bias_grad_%dx%d_%s" % (n, kk, act)
        fn, specs = variants[name]
        err, y = (rand(spec) for spec in specs)
        e64 = numpy.asarray(err.astype(jnp.float32), numpy.float64)
        d = A.ACTIVATIONS[act][1](
            numpy, numpy.asarray(y.astype(jnp.float32), numpy.float64))
        want = (e64 if isinstance(d, float) else e64 * d).sum(axis=0)
        res = numpy.asarray(fn(err, y), numpy.float64)
        check(res.shape == want.shape,
              "kernel %s: shape %r" % (name, res.shape))
        err = float(numpy.abs(res - want).max())
        settle(name, err, err,
               max(TOL_BIAS_GRAD_ABS,
                   TOL_BIAS_GRAD_REL * float(numpy.abs(want).max())))

    sync = sync_check(*cfg["sync_matmul"])
    print(json.dumps({"device": facts, "errors": errors, "sync": sync}),
          flush=True)


def sync_check(n, reps):
    """Does ``block_until_ready`` wait for the device? Enqueue a long
    chain of matmuls (each feeds the next, so nothing can be elided),
    then time the enqueue, ``block_until_ready`` and a scalar
    read-back separately. Where it blocks, the wait lands in the
    second figure and the read-back is short."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    import numpy
    gen = numpy.random.Generator(numpy.random.PCG64(7))
    a = jnp.asarray(gen.standard_normal((n, n), numpy.float32),
                    jnp.bfloat16)
    w = jnp.asarray(gen.standard_normal((n, n), numpy.float32)
                    / numpy.sqrt(n), jnp.bfloat16)

    @jax.jit
    def chain(a, w):
        c, _ = lax.scan(lambda c, _: (jnp.matmul(c, w), ()), a, None,
                        length=reps)
        return c.astype(jnp.float32).sum()

    float(chain(a, w))                      # compile + warm
    t0 = time.perf_counter()
    y = chain(a, w)
    t1 = time.perf_counter()
    y.block_until_ready()
    t2 = time.perf_counter()
    value = float(y)
    t3 = time.perf_counter()
    check(math.isfinite(value), "sync check: chain is not finite")
    sync = {"matmul_n": n, "chained": reps,
            "enqueue_s": round(t1 - t0, 6),
            "block_until_ready_s": round(t2 - t1, 6),
            "readback_s": round(t3 - t2, 6)}
    print("sync check: %s" % json.dumps(sync), flush=True)
    return sync


# -- entry --------------------------------------------------------------


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, default=1, choices=(1, 4))
    # the parent's own protocol for starting its children
    p.add_argument("--child", choices=("train", "kernels"),
                   help=argparse.SUPPRESS)
    p.add_argument("--size", default="full", help=argparse.SUPPRESS)
    p.add_argument("--device", default="tpu", help=argparse.SUPPRESS)
    p.add_argument("--workdir", help=argparse.SUPPRESS)
    p.add_argument("--leg", default="single", help=argparse.SUPPRESS)
    args = p.parse_args(argv)
    if args.child == "train":
        return child_train(args.size, args.device, args.workdir,
                           args.leg)
    if args.child == "kernels":
        return child_kernels(args.size, args.device)
    t0 = time.monotonic()
    try:
        facts = run_four_chips() if args.chips == 4 else run_single_chip()
    except SmokeFailure as exc:
        print("chip_smoke FAILED: %s" % exc, file=sys.stderr)
        return 1
    print("chip_smoke: all phases passed in %.0fs"
          % (time.monotonic() - t0), flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": facts["platform"], "kind": facts["kind"],
        "count": facts["count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
