"""Seconds the trainer spent lowering jaxprs to MLIR modules before the
window opened: the union of its ``compile_lower`` spans (jax's
``jaxpr_to_mlir_module_duration``, one a program, ``fun`` its name) that
ended before the opening row. None where the program writes no such span."""

from benchmark.layer_metrics.jit_trace_s import phase, union_s


def read(run):
    spans = phase(run, "compile_lower")
    return union_s(spans) if spans else None
