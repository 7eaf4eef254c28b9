"""Seconds XLA spent compiling the trainer's programs before the window
opened: the sum of its ``compile_backend`` spans (jax's
``backend_compile_duration``, one a program) with ``cache`` ``miss`` (not in
the persistent cache; a write to it is inside the span) or ``off`` (no cache)
that ended before the opening row. 0.0 where every program was loaded; None
where the program writes no ``compile_backend`` span.

On stderr, one line: how many programs were compiled and how many loaded,
and the five that cost the most set-up, each with its trace, lower and
backend seconds (a trace includes the traces of the jitted functions it
calls, which are no programs of their own) and the cache's outcome."""

import sys

from benchmark.layer_metrics.jit_trace_s import phase

SHOWN = 5


def program(fun):
    """A program's name as its trace gives it: the lowered module and its
    compile say ``jit(step)`` where the trace says ``step``."""
    head, _, rest = fun.partition("(")
    return rest[:-1] if head.isidentifier() and rest.endswith(")") else fun


def by_program(run):
    """``fun`` -> seconds of each phase and the cache's outcomes."""
    out = {}
    for name, key in (("compile_trace", "trace"), ("compile_lower", "lower"),
                      ("compile_backend", "backend")):
        for s in phase(run, name):
            p = out.setdefault(program(s.get("fun", "")),
                               {"trace": 0.0, "lower": 0.0, "backend": 0.0,
                                "cache": set()})
            p[key] += s["dur_s"]
            if "cache" in s:
                p["cache"].add(s["cache"])
    return out


def read(run):
    spans = phase(run, "compile_backend")
    if not spans:
        return None
    compiled = [s for s in spans if s.get("cache") in ("miss", "off")]
    # a function traced inside another one is part of that program
    programs = {f: p for f, p in by_program(run).items() if p["cache"]}
    dearest = sorted(programs.items(), key=lambda kv: -(
        kv[1]["trace"] + kv[1]["lower"] + kv[1]["backend"]))[:SHOWN]
    print(f"compile: {len(compiled)} programs compiled, "
          f"{len(spans) - len(compiled)} loaded before the window; dearest "
          f"(trace / lower / backend s, cache): " + "; ".join(
              f"{fun} {p['trace']:.3f} / {p['lower']:.3f} / "
              f"{p['backend']:.3f} {'+'.join(sorted(p['cache']))}"
              for fun, p in dearest), file=sys.stderr, flush=True)
    return float(sum(s["dur_s"] for s in compiled))
