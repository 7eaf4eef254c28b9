"""Seconds the trainer spent loading programs from the persistent
compilation cache before the window opened: the sum of its
``compile_backend`` spans (jax's ``backend_compile_duration``, one a
program) with ``cache`` ``hit`` that ended before the opening row. 0.0
where every program was compiled; None where the program writes no
``compile_backend`` span."""

from benchmark.layer_metrics.jit_trace_s import phase


def read(run):
    spans = phase(run, "compile_backend")
    if not spans:
        return None
    return float(sum(s["dur_s"] for s in spans if s.get("cache") == "hit"))
