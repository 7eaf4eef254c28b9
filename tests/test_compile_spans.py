"""The compile phases as spans (utils/resources.py, ``CompileSentry``):
one ``compile_trace`` / ``compile_lower`` / ``compile_backend`` span a
phase and program, from jax's own ``jax.monitoring`` time spans, with the
program's name, the persistent cache's outcome, and the sentry's counters
unchanged beside them."""

import json
import os
import subprocess
import sys

import pytest

from distributed_tensorflow_tpu.utils import resources, telemetry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PHASES = ("compile_trace", "compile_lower", "compile_backend")


@pytest.fixture(autouse=True)
def clean_plane():
    telemetry.configure(logdir=None, enabled=True)
    telemetry.get_tracer().clear()
    resources.activate()
    yield
    telemetry.configure(logdir=None, enabled=True)
    telemetry.get_tracer().clear()
    resources.activate()


def _listening():
    sentry = resources.CompileSentry()
    resources.activate(sentry=sentry)
    resources._install_compile_listener()
    return sentry


def _compile_spans():
    return [r for r in telemetry.last_spans(2048) if r["name"] in PHASES]


def _nested_program(scale):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def inner(x):
        return jnp.tanh(x) * scale

    @jax.jit
    def outer(x):
        return inner(x).sum() + 1.0

    return outer, jnp.ones((4, 4))


def test_a_nested_jit_gives_each_phase_a_span_with_its_program_and_no_cache():
    outer, x = _nested_program(3.0)
    sentry = _listening()
    with telemetry.trace_span("device_chunk", step=0):
        outer(x).block_until_ready()
    spans = _compile_spans()
    chunk = next(r for r in telemetry.last_spans(2048)
                 if r["name"] == "device_chunk")
    # every phase is a child of the span open on the compiling thread
    assert spans and all(r["parent"] == chunk["id"] for r in spans)
    # one trace span a program: the nested jit, and jnp's own jitted
    # functions, are traced inside it and are part of it
    traces = [r for r in spans if r["name"] == "compile_trace"]
    assert [r["fun"] for r in traces] == ["outer"]
    out = traces[0]
    lowered = [r for r in spans if r["name"] == "compile_lower"
               and r["fun"] == "jit(outer)"]
    backend = [r for r in spans if r["name"] == "compile_backend"
               and r["fun"] == "jit(outer)"]
    assert len(lowered) == 1 and len(backend) == 1
    assert lowered[0]["ts"] >= out["ts"] + out["dur_s"] - 1e-6
    assert backend[0]["ts"] >= lowered[0]["ts"] + lowered[0]["dur_s"] - 1e-6
    # the tests run with the persistent cache off
    assert all(r["cache"] == "off" for r in spans
               if r["name"] == "compile_backend")
    # the nested jit is never compiled alone
    assert not any(r["fun"] == "jit(inner)" for r in spans)
    all_backend = [r for r in spans if r["name"] == "compile_backend"]
    assert sentry.compiles_total == len(all_backend)
    assert sentry.compile_time_s == pytest.approx(
        sum(r["dur_s"] for r in all_backend), rel=1e-9)


def test_what_a_lowering_traces_is_part_of_the_program():
    """The PRNG's lowering rules trace jnp functions while the program is
    lowered: one trace span still, the program's."""
    import jax

    key = jax.random.PRNGKey(0)
    draw = jax.jit(lambda k: jax.random.uniform(k, (4,)) * 3.0)
    _listening()
    draw(key).block_until_ready()
    spans = _compile_spans()
    assert [(r["name"], r["fun"]) for r in spans] == [
        ("compile_trace", "<lambda>"), ("compile_lower", "jit(<lambda>)"),
        ("compile_backend", "jit(<lambda>)")]


def test_a_program_already_compiled_gives_no_span():
    outer, x = _nested_program(5.0)
    _listening()
    outer(x).block_until_ready()
    before = len(_compile_spans())
    outer(x).block_until_ready()
    assert len(_compile_spans()) == before


def test_telemetry_off_means_no_compile_spans_and_the_counts_stay():
    telemetry.configure(logdir=None, enabled=False)
    sentry = _listening()
    outer, x = _nested_program(7.0)
    outer(x).block_until_ready()
    assert sentry.compiles_total >= 1 and sentry.compile_time_s > 0
    telemetry.configure(logdir=None, enabled=True)
    assert _compile_spans() == []


def test_no_active_sentry_means_no_compile_spans():
    resources._install_compile_listener()
    outer, x = _nested_program(9.0)
    outer(x).block_until_ready()
    assert _compile_spans() == []


CACHED = """
import json, sys
import jax, jax.numpy as jnp
jax.config.update("jax_compilation_cache_dir", sys.argv[1])
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
from distributed_tensorflow_tpu.utils import resources, telemetry
sentry = resources.CompileSentry()
resources.activate(sentry=sentry)
resources._install_compile_listener()
f = jax.jit(lambda x: jnp.tanh(x @ x.T).sum())
f(jnp.ones((64, 64))).block_until_ready()
spans = [r for r in telemetry.last_spans(2048)
         if r["name"] == "compile_backend"]
print(json.dumps({"spans": spans, "scalars": sentry.scalars()}))
"""


def test_the_first_process_compiles_and_stores_the_second_loads(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_ENABLE_COMPILATION_CACHE="true", PYTHONPATH=REPO)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    out = []
    for _ in range(2):
        p = subprocess.run(
            [sys.executable, "-c", CACHED, str(tmp_path / "cache")],
            cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
        assert p.returncode == 0, p.stderr[-3000:]
        out.append(json.loads(p.stdout.strip().splitlines()[-1]))
    first, second = out
    assert first["spans"] and all(r["cache"] == "miss" and r["stored"]
                                  for r in first["spans"])
    assert first["scalars"]["compile_cache_hits"] == 0
    assert len(second["spans"]) == len(first["spans"])
    assert all(r["cache"] == "hit" and 0 < r["retrieval_s"] <= r["dur_s"]
               for r in second["spans"])
    for run in out:
        # the sentry's counters are the spans' count and sum
        assert run["scalars"]["compiles_total"] == len(run["spans"])
        assert run["scalars"]["compile_time_s"] == pytest.approx(
            sum(r["dur_s"] for r in run["spans"]), abs=1e-4)
    assert second["scalars"]["compile_cache_hits"] == len(second["spans"])
