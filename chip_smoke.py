#!/usr/bin/env python
"""The quickest proof that this tree runs on a TPU chip.

    python chip_smoke.py               # one chip: train -> checkpoint -> serve
    python chip_smoke.py --multichip   # four chips: sync DP, the mode sweep,
                                       # ZeRO and the pipeline schedules

Drives the entry points a user would call — ``mnist_dist.py`` and
``python -m distributed_tensorflow_tpu.serving`` — at the widths the repo
publishes (weights random from ``--seed``, data the procedural set), checks
what comes out, and exits non-zero on the first thing that is wrong. The
last line of stdout is the one JSON object the contract fixes,
``{"ok": true, "device": {...}}``; nothing else goes on that line and it is
printed only when every phase passed.

ONE process holds the chip at a time. This process never imports JAX:
every phase is a child that has exited before the next one starts (the
server is shut down and waited for). Children that need to look INSIDE a
program — is the Mosaic kernel in the compiled step, what does the compiler
say about memory, do two schedules give the same parameters — run as
``python -c "import chip_smoke; chip_smoke._child_*()"``.

Anything but a ``tpu`` platform fails at the gate and no phase runs: a CPU
pass here would be a statement about XLA:CPU.
"""

from __future__ import annotations

import json
import math
import os
import re
import shutil
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
#: logdirs, checkpoints and child logs: in the checkout, ignored by git,
#: wiped at the start of every run (the training loop auto-restores from
#: whatever its --logdir holds)
WORK = os.path.join(HERE, ".chip_smoke")
#: what comes back from the chip tool: the report only, never a checkpoint
REPORT = os.path.join(HERE, "chiprun_out", "chip_smoke.json")

CNN_FLAGS = ["--model", "deep_cnn", "--device_data", "--bf16", "--prng",
             "rbg", "--optimizer", "adam", "--batch_size", "2048",
             "--device_chunk", "50", "--display_step", "50"]
CNN_STEPS = 200
# bench.py's published large-vocab long-context LM (LM_BIGV_*)
LM_FLAGS = ["--dataset", "lm", "--model", "lm", "--d_model", "256",
            "--num_heads", "4", "--num_blocks", "4", "--seq_len", "8192",
            "--vocab_size", "32768", "--batch_size", "4", "--attn_block",
            "512", "--ce_block", "512", "--bf16", "--optimizer", "adam"]
LM_STEPS = 5
SERVE_REQUESTS = 3
SERVE_PROMPT_LEN = 8
SERVE_NEW_TOKENS = 16
PHASE_TIMEOUT_S = 600
#: the CNN trainer's chunk takes ~20 s to compile on the chip; a process
#: that loaded its programs from the persistent cache spends under a second
WARM_COMPILE_S = 5.0
DP_STEPS = 10
#: The four-chip comparisons run twice: in f32
#: (``jax.default_matmul_precision("highest")``), where two programs for
#: one function differ only in the order of their f32 sums, and at the
#: TPU's default precision, where an f32 matmul multiplies in one bf16
#: pass. Every figure below is (bound in f32, bound at default precision);
#: the chips' own figures (v5e 2x2, PR 21) stand beside each.
#:
#: Sync DP on four chips vs make_train_step on one of them, same ten
#: batches, dropout off: largest relative difference of a loss. The run
#: climbs from 4.8 to 6.3 before it falls and amplifies whatever it is
#: given: 5e-7 for seven steps, 1.7e-4 at the tenth in f32; 2.7e-3 at
#: default precision. A DP step that sums where it should average, or
#: loses a shard, moves the loss by tens of percent within a few steps.
DP_LOSS_RTOL = (1e-3, 1e-2)
#: Pairs the docs hold equal: largest |difference| of any parameter over
#: the largest magnitude of its leaf. In f32 the pipeline schedules are
#: held to the 1e-6 of tests/test_pp_zb.py (chips: 2.8e-7, interleaved vs
#: gpipe 0) and ZeRO-1 vs replicated DP to 1e-5 (chips: 2.1e-6; bitwise
#: only on XLA:CPU). At default precision one bound for all: bf16 keeps 8
#: bits, so each product is rounded by up to 2**-9 = 2e-3, and a leaf that
#: starts at zero is after two sgd steps nothing but its gradient, which
#: passed ~100 such matmuls forward and back through 8 blocks: 2e-2 if the
#: roundings are independent, 2e-1 if they line up (chips: 4.4e-2 for
#: zero-bubble, 8.8e-7 for ZeRO-1, 0 for interleaved). A schedule that
#: drops, doubles or misplaces one of the four microbatches moves such a
#: leaf by 2.5e-1. The child also prints what the precision alone does to
#: ONE schedule (gpipe at default precision vs gpipe in f32), so that the
#: bf16 explanation is a reading and not a claim.
PP_RTOL = (1e-6, 1e-1)
ZERO_RTOL = (1e-5, 1e-1)


class SmokeError(Exception):
    pass


def _say(msg: str) -> None:
    print(msg, flush=True)


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeError(what)
    _say(f"  ok: {what}")


def _tail(path: str, n: int = 40) -> str:
    try:
        with open(path, errors="replace") as f:
            return "".join(f.readlines()[-n:])
    except OSError as e:
        return f"<{e}>"


def _run(name: str, cmd: list, timeout: int = PHASE_TIMEOUT_S) -> str:
    """One child to its end; its output goes to ``WORK/<name>.log`` and
    comes back as text. Non-zero exit or a timeout fails the smoke."""
    log = os.path.join(WORK, f"{name}.log")
    t0 = time.perf_counter()
    with open(log, "w") as f:
        try:
            rc = subprocess.run(cmd, cwd=HERE, stdout=f,
                                stderr=subprocess.STDOUT,
                                timeout=timeout).returncode
        except subprocess.TimeoutExpired:
            raise SmokeError(f"{name}: no end after {timeout}s\n"
                             f"{_tail(log)}") from None
    _say(f"  [{name}] exit {rc} after {time.perf_counter() - t0:.1f}s")
    if rc != 0:
        raise SmokeError(f"{name}: exit code {rc}\n{_tail(log)}")
    with open(log, errors="replace") as f:
        return f.read()


def _child(name: str, fn: str, *args: str,
           timeout: int = PHASE_TIMEOUT_S) -> dict:
    """Run ``chip_smoke.<fn>(*args)`` in a fresh interpreter; the child's
    last stdout line is its JSON result."""
    out = _run(name, [sys.executable, "-c",
                      f"import sys, chip_smoke; "
                      f"chip_smoke.{fn}(*sys.argv[1:])", *args],
               timeout=timeout)
    for line in out.splitlines():
        if not line.startswith("{"):
            _say(f"    {line}")
    return json.loads(out.strip().splitlines()[-1])


# ------------------------------------------------------------------- gate


def _require_tpu(device: dict, count: int) -> None:
    if device["platform"] != "tpu":
        raise SmokeError(
            f"gate: the platform is {device['platform']!r}, not 'tpu' — "
            f"no phase runs off the chip")
    if device["count"] != count:
        raise SmokeError(f"gate: this run needs {count} chip(s) and JAX "
                         f"reports {device['count']}")


def gate(count: int) -> dict:
    for needed in ("mnist_dist.py", "distributed_tensorflow_tpu"):
        if not os.path.exists(os.path.join(HERE, needed)):
            raise SmokeError(f"gate: {needed} is not beside chip_smoke.py "
                             f"— this is not a checkout of the repo")
    info = _child("gate", "_child_gate", timeout=300)
    _say(f"gate: {json.dumps(info)}")
    if not info["native_data_plane"]:
        _say(f"  native data plane NOT loaded ({info['native_error']}): "
             f"the NumPy path serves the input pipeline")
    _require_tpu(info["device"], count)
    return info


def _child_gate() -> None:
    import jax
    import jaxlib

    from distributed_tensorflow_tpu import native
    from distributed_tensorflow_tpu.utils.compile_cache import (
        compile_cache_dir,
    )

    d = jax.devices()
    try:
        from importlib.metadata import version

        libtpu = version("libtpu")
    except Exception as e:  # noqa: BLE001 — reported, not needed
        libtpu = f"unknown ({e})"
    print(json.dumps({
        "device": {"platform": d[0].platform, "kind": d[0].device_kind,
                   "count": len(d)},
        "jax": jax.__version__, "jaxlib": jaxlib.__version__,
        "libtpu": libtpu,
        "compile_cache_dir": compile_cache_dir(),
        "native_data_plane": native.available(),
        "native_error": native.build_error(),
    }))


# ---------------------------------------------------------------- trainer

_DISPLAY = re.compile(r"step:\s+(\d+) mini_batch loss:\s+(\S+) "
                      r"training accuracy:\s+(\S+)")
_TEST = re.compile(r"test accuracy:\s+(\S+) test loss:\s+(\S+)")


def _scalars(logdir: str) -> list:
    with open(os.path.join(logdir, "metrics.jsonl")) as f:
        return [json.loads(line) for line in f if line.strip()]


def _last(rows: list, key: str):
    return next((r[key] for r in reversed(rows) if key in r), None)


def train(name: str, flags: list, steps: int,
          untrained_loss: float | None = None) -> dict:
    """``python mnist_dist.py <flags>`` to its end, then what it printed
    and left on disk: losses finite and falling, a test evaluation, a
    checkpoint at the last step. A run too short to fall for certain
    (each display evaluates a fresh batch) gives ``untrained_loss``
    instead: every loss must sit within 1% of it."""
    logdir = os.path.join(WORK, name)
    out = _run(name, [sys.executable, "mnist_dist.py", *flags,
                      "--training_iter", str(steps), "--logdir", logdir])
    shown = [(int(s), float(l)) for s, l, _ in _DISPLAY.findall(out)]
    test = _TEST.search(out)
    _check(len(shown) >= 2 and test is not None,
           f"{name}: display losses {shown} and a test evaluation printed")
    losses = [l for _, l in shown]
    test_acc, test_loss = float(test.group(1)), float(test.group(2))
    _check(all(math.isfinite(v) for v in losses + [test_loss]),
           f"{name}: every loss finite")
    if untrained_loss is None:
        _check(losses[-1] < losses[0],
               f"{name}: loss fell {losses[0]:.4f} -> {losses[-1]:.4f} "
               f"(step {shown[0][0]} -> {shown[-1][0]}); test accuracy "
               f"{test_acc:.4f}, test loss {test_loss:.4f}")
    else:
        _check(all(abs(v - untrained_loss) < 0.01 * untrained_loss
                   for v in losses + [test_loss]),
               f"{name}: losses {[round(v, 4) for v in losses]} and test "
               f"loss {test_loss:.4f} within 1% of an untrained model's "
               f"{untrained_loss:.4f}")
    with open(os.path.join(logdir, "checkpoint")) as f:
        latest = json.load(f)["latest_step"]
    on_disk = [p for p in os.listdir(logdir) if p.startswith(f"ckpt-{steps}")]
    _check(latest == steps and bool(on_disk),
           f"{name}: checkpoint of step {steps} on disk ({on_disk})")
    rows = _scalars(logdir)
    res = {"logdir": logdir, "display": shown, "final_loss": losses[-1],
           "test_accuracy": test_acc, "test_loss": test_loss,
           "compile_seconds": _last(rows, "compile_time_s"),
           "compiles": _last(rows, "compiles_total"),
           "compile_cache_hits": _last(rows, "compile_cache_hits"),
           "images_per_sec": _last(rows, "images_per_sec"),
           "hbm_peak_bytes": _last(rows, "hbm_peak_bytes")}
    _say(f"  {name}: compile {res['compile_seconds']}s over "
         f"{res['compiles']:.0f} programs "
         f"({res['compile_cache_hits']:.0f} loaded from the persistent "
         f"cache), {res['images_per_sec']} "
         f"examples/s at the last display (smoke output, not a benchmark)")
    return res


def _trainer_flags(argv: list):
    """``argv`` parsed as ``mnist_dist.py`` parses it — its flag table, its
    cache placement (flags.run), its PRNG choice — for a child that
    compiles the trainer's program again to look inside it."""
    import mnist_dist
    from distributed_tensorflow_tpu.utils.compile_cache import (
        enable_compile_cache,
    )

    mnist_dist.FLAGS._parse(["chip_smoke"] + list(argv))
    enable_compile_cache()
    mnist_dist.set_prng_impl()
    return mnist_dist.FLAGS


def _timed_compile(lowered):
    """(compiled, seconds, served from the persistent cache?)."""
    import jax

    hits = []
    jax.monitoring.register_event_listener(
        lambda event, **kw: hits.append(event)
        if event == "/jax/compilation_cache/cache_hits" else None)
    t0 = time.perf_counter()
    compiled = lowered.compile()
    return compiled, time.perf_counter() - t0, bool(hits)


def _child_cnn_program(*argv: str) -> None:
    """The device-resident chunk ``mnist_dist.py <argv>`` trains with —
    model, optimizer and state from the loop's own builder, which is
    where ``--pallas`` is decided — and what the compiler made of it."""
    import jax

    FLAGS = _trainer_flags(argv)
    from distributed_tensorflow_tpu.data import read_data_sets
    from distributed_tensorflow_tpu.data.device_data import put_device_data
    from distributed_tensorflow_tpu.training.device_step import (
        make_device_train_step,
    )
    from distributed_tensorflow_tpu.training.loop import build_training_for

    ds = read_data_sets(FLAGS.data_dir, one_hot=True, dataset=FLAGS.dataset,
                        seed=FLAGS.seed,
                        validation_size=FLAGS.validation_size)
    model, opt, state = build_training_for(FLAGS, ds.meta)
    step = make_device_train_step(model, opt, FLAGS.batch_size,
                                  keep_prob=FLAGS.keep_prob,
                                  chunk=FLAGS.device_chunk)
    compiled, secs, hit = _timed_compile(
        step.lower(state, put_device_data(ds.train)))
    print(json.dumps({
        "mosaic_kernel": "tpu_custom_call" in compiled.as_text(),
        "compile_seconds": round(secs, 3), "cache_hit": hit,
        "data_source": ds.source,
        "train_examples": int(ds.train.num_examples),
        "platform": jax.devices()[0].platform}))


def _child_lm_program(*argv: str) -> None:
    """The LM train step ``mnist_dist.py <argv>`` runs (host-fed, one
    device): the compiler's memory figures, then one step on the chip
    and the device's own peak."""
    import jax

    FLAGS = _trainer_flags(argv)
    from distributed_tensorflow_tpu.data.lm import LMDataSet
    from distributed_tensorflow_tpu.data.pipeline import (
        batch_iterator,
        prefetch_to_device,
    )
    from distributed_tensorflow_tpu.training import make_train_step
    from distributed_tensorflow_tpu.training.loop import build_training_for

    # the shapes and dtypes of the trainer's batches; a few sequences are
    # enough to make one
    split = LMDataSet(2 * FLAGS.batch_size, FLAGS.seq_len, FLAGS.vocab_size,
                      seed=FLAGS.seed)
    model, opt, state = build_training_for(
        FLAGS, {"kind": "lm", "seq_len": FLAGS.seq_len,
                "vocab_size": FLAGS.vocab_size})
    step = make_train_step(model, opt, keep_prob=FLAGS.keep_prob)
    batches = prefetch_to_device(
        batch_iterator(split, FLAGS.batch_size, raw=FLAGS.raw_input), size=2)
    batch = next(batches)
    compiled, secs, hit = _timed_compile(step.lower(state, batch))
    ma = compiled.memory_analysis()
    state, m = compiled(state, batch)
    loss = float(m["loss"])
    batches.close()
    stats = jax.devices()[0].memory_stats() or {}
    print(json.dumps({
        "compile_seconds": round(secs, 3), "cache_hit": hit,
        "temp_bytes": int(ma.temp_size_in_bytes),
        "argument_bytes": int(ma.argument_size_in_bytes),
        "output_bytes": int(ma.output_size_in_bytes),
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        "bytes_limit": stats.get("bytes_limit"),
        "one_step_loss": loss}))


# ----------------------------------------------------------------- server


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _http(url: str, payload: dict | None = None, timeout: float = 10.0):
    data = None if payload is None else json.dumps(payload).encode()
    req = urllib.request.Request(
        url, data=data, headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}")


def _prompts(vocab: int) -> list:
    return [[(7919 * (i + 1) + 104729 * j) % vocab
             for j in range(SERVE_PROMPT_LEN)]
            for i in range(SERVE_REQUESTS)]


def serve(name: str, lm_logdir: str, scheduler: str, served_step: int,
          vocab: int) -> list:
    """Start the server on the trainer's logdir, wait for /healthz, send
    the requests, shut it down and wait for it to be gone."""
    port = _free_port()
    log = os.path.join(WORK, f"{name}.log")
    cmd = [sys.executable, "-m", "distributed_tensorflow_tpu.serving",
           *LM_FLAGS, "--logdir", lm_logdir, "--serve_port", str(port),
           "--serve_scheduler", scheduler, "--serve_reload_secs", "0",
           "--serve_timeout_ms", str(PHASE_TIMEOUT_S * 1000)]
    t0 = time.perf_counter()
    with open(log, "w") as f:
        proc = subprocess.Popen(cmd, cwd=HERE, stdout=f,
                                stderr=subprocess.STDOUT)
    base = f"http://127.0.0.1:{port}"
    answers = []
    try:
        while True:
            if proc.poll() is not None:
                raise SmokeError(f"{name}: the server exited with "
                                 f"{proc.returncode} before /healthz\n"
                                 f"{_tail(log)}")
            if time.perf_counter() - t0 > PHASE_TIMEOUT_S:
                raise SmokeError(f"{name}: no /healthz 200 after "
                                 f"{PHASE_TIMEOUT_S}s\n{_tail(log)}")
            try:
                status, health = _http(base + "/healthz", timeout=2.0)
                if status == 200:
                    break
            except OSError:
                pass
            time.sleep(0.5)
        _say(f"  [{name}] /healthz 200 after "
             f"{time.perf_counter() - t0:.1f}s: {json.dumps(health)}")
        for i, prompt in enumerate(_prompts(vocab)):
            t1 = time.perf_counter()
            status, body = _http(
                base + "/v1/generate",
                {"prompt": prompt, "max_new_tokens": SERVE_NEW_TOKENS},
                timeout=PHASE_TIMEOUT_S)
            new = body.get("tokens", [])[len(prompt):]
            _check(status == 200 and len(new) == SERVE_NEW_TOKENS
                   and body.get("served_step") == served_step
                   and all(0 <= t < vocab for t in new),
                   f"{name}: request {i} -> {status}, {len(new)} new "
                   f"tokens, served_step {body.get('served_step')} in "
                   f"{time.perf_counter() - t1:.2f}s")
            answers.append(new)
    finally:
        if proc.poll() is None:
            proc.send_signal(signal.SIGINT)
            try:
                proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait()
                raise SmokeError(f"{name}: the server ignored SIGINT for "
                                 f"60s and was killed\n{_tail(log)}") \
                    from None
    _check(proc.returncode == 0, f"{name}: the server shut down cleanly "
                                 f"(exit {proc.returncode})")
    return answers


# ----------------------------------------------------------- default run


def one_chip(report: dict) -> None:
    cnn_phases(report)
    lm_and_server_phases(report)


def cnn_phases(report: dict) -> None:
    _say("== trainer: deep CNN at reference width, --pallas ==")
    pallas_flags = CNN_FLAGS + ["--pallas"]
    cnn_p = train("cnn_pallas", pallas_flags, CNN_STEPS)
    _say("== trainer: the same, XLA's own FC layer ==")
    cnn_x = train("cnn_xla", CNN_FLAGS, CNN_STEPS)
    _say(f"  final display loss: --pallas {cnn_p['final_loss']:.6f} | "
         f"xla {cnn_x['final_loss']:.6f}; test accuracy "
         f"{cnn_p['test_accuracy']:.4f} | {cnn_x['test_accuracy']:.4f}")
    _say("== the same --pallas trainer again, a later process ==")
    again = train("cnn_pallas_again", pallas_flags, CNN_STEPS // 2)
    # the trainer's own count of /jax/compilation_cache/cache_hits. A
    # machine whose cache directory came with entries serves the first
    # process from it too, and its count says so
    _say(f"  the run's programs (the chunk and the display eval): "
         f"{cnn_p['compile_seconds']}s, {cnn_p['compile_cache_hits']:.0f} "
         f"loaded from the persistent cache in the first process | "
         f"{again['compile_seconds']}s, "
         f"{again['compile_cache_hits']:.0f} loaded in this later one")
    _check(again["compile_cache_hits"] >= 1
           and again["compile_seconds"] < WARM_COMPILE_S,
           "the later process loaded its programs from the persistent "
           "cache and compiled none that takes seconds")
    _say("== the compiled CNN chunks, looked into ==")
    prog_p = _child("cnn_pallas_program", "_child_cnn_program",
                    *pallas_flags)
    prog_x = _child("cnn_xla_program", "_child_cnn_program", *CNN_FLAGS)
    _say(f"  data_source: {prog_p['data_source']} "
         f"({prog_p['train_examples']} training examples)")
    _check(prog_p["mosaic_kernel"] and not prog_x["mosaic_kernel"],
           "tpu_custom_call is in the compiled --pallas chunk and not in "
           "the other")
    # a Mosaic kernel is serialized into the program with its Python call
    # stack as debug locations, so the --pallas chunk is found again only
    # from the call path that stored it (the trainer's, above) — reported
    _say(f"  rebuilt from this script: --pallas chunk "
         f"{prog_p['compile_seconds']}s (cache hit {prog_p['cache_hit']}) "
         f"| xla chunk {prog_x['compile_seconds']}s (cache hit "
         f"{prog_x['cache_hit']})")
    report.update(cnn_pallas=cnn_p, cnn_xla=cnn_x, cnn_pallas_again=again,
                  cnn_pallas_program=prog_p, cnn_xla_program=prog_x)


def lm_and_server_phases(report: dict) -> None:
    _say("== trainer: causal LM, V=32768 S=8192 ==")
    vocab = int(LM_FLAGS[LM_FLAGS.index("--vocab_size") + 1])
    # five adam steps at lr 1e-3 do not move a 32768-way softmax for
    # certain; what they can show is the streamed CE head's normalizer:
    # an untrained LM scores ln V
    lm = train("lm", LM_FLAGS + ["--display_step", "1"], LM_STEPS,
               untrained_loss=math.log(vocab))
    lm_prog = _child("lm_program", "_child_lm_program", *LM_FLAGS)
    _say(f"  LM step: compiler temp {lm_prog['temp_bytes']} B, arguments "
         f"{lm_prog['argument_bytes']} B; memory_stats peak_bytes_in_use "
         f"{lm_prog['peak_bytes_in_use']} B of {lm_prog['bytes_limit']} "
         f"(trainer's own peak {lm['hbm_peak_bytes']}); compile "
         f"{lm_prog['compile_seconds']}s, cache hit "
         f"{lm_prog['cache_hit']}")
    _check(lm_prog["peak_bytes_in_use"] is not None,
           "the device reports memory_stats")

    _say("== server: whole-batch scheduler ==")
    whole = serve("serve_whole_batch", lm["logdir"], "whole_batch",
                  LM_STEPS, vocab)
    _say("== server: continuous scheduler ==")
    cont = serve("serve_continuous", lm["logdir"], "continuous",
                 LM_STEPS, vocab)
    for i, (a, b) in enumerate(zip(whole, cont)):
        _say(f"  request {i}: whole_batch {a}")
        _say(f"  request {i}: continuous  {b}")
    _check(whole == cont, "continuous batching returns the whole-batch "
                          "tokens, bit for bit")
    report.update(lm=lm, lm_program=lm_prog, serve_whole_batch=whole,
                  serve_continuous=cont)


# ------------------------------------------------------------ four chips


def multichip(report: dict) -> None:
    _say("== sync DP through the CLI: device-resident, 2048 per chip ==")
    flags = list(CNN_FLAGS)
    per_chip = int(flags[flags.index("--batch_size") + 1])
    flags[flags.index("--batch_size") + 1] = str(4 * per_chip)
    dp = train("dp_cli", flags, CNN_STEPS)
    _say("== the same math on four chips: DP, the mode sweep, ZeRO, PP ==")
    res = _child("multichip", "_child_multichip", timeout=1200)
    report.update(dp_cli=dp, multichip=res)
    for i, tag in enumerate(("f32", "default_precision")):
        got = res[tag]
        _check(got["dp_vs_one_chip_max_rel_loss_diff"] <= DP_LOSS_RTOL[i],
               f"{tag}: sync DP on {res['n_devices']} chips follows one "
               f"chip over {DP_STEPS} sgd steps: largest relative loss "
               f"difference {got['dp_vs_one_chip_max_rel_loss_diff']:.3e} "
               f"<= {DP_LOSS_RTOL[i]}")
        # the DP parameters ride the same amplification as the DP losses
        # and are printed by the child, not held to a bound of their own
        for pair, d in got["param_diffs"].items():
            if pair == "sync DP vs one chip":
                continue
            bound = (ZERO_RTOL if pair.startswith("ZeRO") else PP_RTOL)[i]
            _check(d["max_rel"] <= bound,
                   f"{tag}: {pair}: largest parameter difference "
                   f"{d['max_abs']:.3e} absolute, {d['max_rel']:.3e} of "
                   f"its leaf's scale <= {bound} (bitwise: "
                   f"{d['max_abs'] == 0.0})")
    for what, d in res["precision_alone"].items():
        _say(f"  default precision vs f32, {what}: {d}")
    _check(all(b > 0 for b in res["bytes_in_use"]),
           f"memory in use on every device while the states were live: "
           f"{res['bytes_in_use']}")


def _child_multichip() -> None:
    import importlib.util

    import jax
    import numpy as np

    from distributed_tensorflow_tpu.utils.compile_cache import (
        enable_compile_cache,
    )

    enable_compile_cache()
    from distributed_tensorflow_tpu.data.lm import LMDataSet
    from distributed_tensorflow_tpu.data.synthetic import synthetic_digits
    from distributed_tensorflow_tpu.models import DeepCNN
    from distributed_tensorflow_tpu.models.transformer import TransformerLM
    from distributed_tensorflow_tpu.parallel import (
        MeshSpec,
        make_dp_train_step,
        make_mesh,
        shard_batch,
    )
    from distributed_tensorflow_tpu.parallel.data_parallel import (
        replicate_state,
    )
    from distributed_tensorflow_tpu.parallel.pipeline_parallel import (
        fetch_state_pp,
        make_pp_train_step,
        shard_state_pp,
        stage_batch_pp,
    )
    from distributed_tensorflow_tpu.parallel.zero import (
        fetch_state_zero,
        make_zero_train_step,
        shard_state_zero,
    )
    from distributed_tensorflow_tpu.training import (
        adam,
        create_train_state,
        get_optimizer,
        make_train_step,
    )

    spec = importlib.util.spec_from_file_location(
        "__graft_entry__", os.path.join(HERE, "__graft_entry__.py"))
    graft = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(graft)

    devices = jax.devices()
    n = len(devices)
    mesh = make_mesh()
    pp_mesh = make_mesh(MeshSpec(data=n // 2, model=2))
    placed = graft._check_placement

    def diff(a, b):
        out = {"max_abs": 0.0, "max_rel": 0.0}
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
            x = np.asarray(x, np.float64)
            y = np.asarray(y, np.float64)
            d = float(np.abs(x - y).max())
            out["max_abs"] = max(out["max_abs"], d)
            out["max_rel"] = max(out["max_rel"],
                                 d / (float(np.abs(y).max()) + 1e-30))
        return out

    cnn = DeepCNN()
    xs, labels = synthetic_digits(DP_STEPS * 256, seed=0)
    ys = np.asarray(jax.nn.one_hot(labels, 10))
    lm = TransformerLM(vocab_size=16, seq_len=32, d_model=32, num_heads=2,
                       num_blocks=8)
    lm_ds = LMDataSet(64, seq_len=32, vocab_size=16, seed=11)
    lm_batches = [lm_ds.next_batch(4 * n) for _ in range(2)]

    in_use = [0] * n

    def sample_memory() -> None:
        """Largest ``bytes_in_use`` seen per device, read while a
        comparison's states are live."""
        for i, d in enumerate(devices):
            in_use[i] = max(in_use[i], int(
                (d.memory_stats() or {}).get("bytes_in_use", 0)))

    def compare(tag: str) -> dict:
        """Every pair the docs hold equal, run side by side from one
        seed on the same batches."""
        # sync DP over every chip vs make_train_step on one of them,
        # dropout off; plain sgd, whose update is linear in the gradient
        # (adam's first steps turn a last-bit difference of a near-zero
        # gradient into a whole learning rate)
        sgd = get_optimizer("sgd", 0.01)
        state0 = create_train_state(cnn, sgd, seed=0)
        dp_step = make_dp_train_step(cnn, sgd, mesh, keep_prob=1.0,
                                     donate=False)
        one_step = make_train_step(cnn, sgd, keep_prob=1.0, donate=False)
        s_dp = replicate_state(mesh, state0)
        s_one = jax.device_put(state0, devices[-1])
        worst, one_losses = 0.0, []
        for i in range(DP_STEPS):
            b = (xs[i * 256:(i + 1) * 256], ys[i * 256:(i + 1) * 256])
            s_dp, m_dp = dp_step(s_dp, shard_batch(mesh, b))
            s_one, m_one = one_step(s_one, jax.device_put(b, devices[-1]))
            placed(s_dp.params, mesh, f"sync DP step {i}")
            l_dp, l_one = float(m_dp["loss"]), float(m_one["loss"])
            worst = max(worst, abs(l_dp - l_one) / abs(l_one))
            one_losses.append(l_one)
            print(f"[{tag}] dp step {i}: loss on {n} chips {l_dp:.7f} | "
                  f"on {devices[-1]} {l_one:.7f}")
        sample_memory()
        diffs = {"sync DP vs one chip": diff(
            jax.device_get(s_dp.params), jax.device_get(s_one.params))}

        # ZeRO-1 against replicated DP: adam (its state is what ZeRO
        # shards), dropout on (same rng folds)
        opt = adam(1e-3)
        state0 = create_train_state(cnn, opt, seed=0)
        batch = shard_batch(mesh, (xs[:256], ys[:256]))
        dp = make_dp_train_step(cnn, opt, mesh, keep_prob=0.8,
                                donate=False)
        z = make_zero_train_step(cnn, opt, mesh, 1, keep_prob=0.8,
                                 donate=False)
        s_dp = replicate_state(mesh, state0)
        s_z = shard_state_zero(state0, mesh, 1)
        for i in range(3):
            s_dp, _ = dp(s_dp, batch)
            s_z, _ = z(s_z, batch)
            placed(s_z.params, mesh, f"ZeRO-1 step {i}")
        sample_memory()
        diffs["ZeRO-1 vs replicated DP"] = diff(
            jax.device_get(s_dp).params,
            fetch_state_zero(s_z, cnn, 1).params)

        # the three pipeline schedules on a 2-stage axis, dropout on
        pp_sgd = get_optimizer("sgd", 0.05)
        base = create_train_state(lm, pp_sgd, seed=0)

        def run_pp(v, schedule):
            st = shard_state_pp(base, pp_mesh, virtual_stages=v)
            step = make_pp_train_step(lm, pp_sgd, pp_mesh, microbatches=4,
                                      keep_prob=0.5, donate=False,
                                      virtual_stages=v, schedule=schedule)
            for i, b in enumerate(lm_batches):
                st, m = step(st, stage_batch_pp(pp_mesh, b))
                placed(st.params, pp_mesh, f"PP {schedule} V={v} step {i}")
            sample_memory()
            return fetch_state_pp(st, lm, k_stages=2,
                                  virtual_stages=v).params

        gpipe = run_pp(1, "gpipe")
        inter = run_pp(2, "interleaved")
        diffs["PP interleaved (V=2) vs gpipe"] = diff(inter, gpipe)
        diffs["PP zero-bubble (V=1) vs gpipe"] = diff(run_pp(1, "zb"),
                                                      gpipe)
        diffs["PP zero-bubble (V=2) vs interleaved"] = diff(
            run_pp(2, "zb"), inter)
        for pair, d in diffs.items():
            print(f"[{tag}] {pair}: {d}")
        return ({"dp_vs_one_chip_max_rel_loss_diff": worst,
                 "param_diffs": diffs}, one_losses, gpipe)

    # in f32, then as users run it (a TPU multiplies f32 operands in one
    # bf16 pass unless asked: two programs for one function then differ
    # by bf16 roundings wherever XLA fuses them differently)
    with jax.default_matmul_precision("highest"):
        f32, one_f32, gpipe_f32 = compare("f32")
    default, one_default, gpipe_default = compare("default precision")
    # what the precision alone does to ONE program: the scale the
    # default-precision differences above are read against
    precision_alone = {
        "one chip, largest relative loss difference": max(
            abs(a - b) / abs(b) for a, b in zip(one_default, one_f32)),
        "PP gpipe parameters": diff(gpipe_default, gpipe_f32)}

    # the mode sweep on the real devices
    graft.dryrun_multichip(n)

    print(json.dumps({"n_devices": n, "f32": f32,
                      "default_precision": default,
                      "precision_alone": precision_alone,
                      "bytes_in_use": in_use}))


# ------------------------------------------------------------------- main


def main(argv: list) -> int:
    if argv not in ([], ["--multichip"]):
        print(__doc__, file=sys.stderr)
        return 2
    four = argv == ["--multichip"]
    shutil.rmtree(WORK, ignore_errors=True)
    os.makedirs(WORK)
    os.makedirs(os.path.dirname(REPORT), exist_ok=True)
    report: dict = {"mode": "multichip" if four else "one_chip"}
    t0 = time.perf_counter()
    try:
        info = gate(4 if four else 1)
        report["gate"] = info
        (multichip if four else one_chip)(report)
    except SmokeError as e:
        print(f"chip_smoke: FAILED — {e}", file=sys.stderr, flush=True)
        return 1
    finally:
        report["seconds"] = round(time.perf_counter() - t0, 1)
        with open(REPORT, "w") as f:
            json.dump(report, f, indent=1)
        # checkpoints are hundreds of MB: keep the logs, drop the rest
        for name in os.listdir(WORK):
            if os.path.isdir(os.path.join(WORK, name)):
                shutil.rmtree(os.path.join(WORK, name), ignore_errors=True)
    _say(f"chip_smoke: every phase passed in {report['seconds']}s")
    print(json.dumps({"ok": True, "device": info["device"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
