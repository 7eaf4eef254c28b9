"""Drives the whole of a run on the CPU at a tiny size, past the harness's
look for a chip, optionally with the timed path broken underneath.

    python tests/benchmark/run_tiny.py <root> <seed> <fault> [<cell>]

``cell`` is ``tiny.CELL`` unless given (``tiny.SWITCH_CELL`` in a root that
``tiny.add_switch_family`` has been at).

``fault``: ``none``; ``state_unchanged`` (the step returns its parameters as
they were); ``half_batch`` (half of the batch left out, the mean taken over
the rest); ``no_exchange`` (the gradient exchange between chips left out).
"""

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)


def plant(fault):
    from distributed_tensorflow_tpu.training import device_step

    if fault == "state_unchanged":
        device_step.apply_updates = lambda params, updates: params
    elif fault == "half_batch":
        whole = device_step.loss_and_metrics

        def half(model, params, batch, **kw):
            n = batch[0].shape[0] // 2
            return whole(model, params, (batch[0][:n], batch[1][:n]), **kw)

        device_step.loss_and_metrics = half
    elif fault == "no_exchange":
        class Lax:
            def __getattr__(self, name):
                from jax import lax

                if name == "pmean":
                    return lambda x, axis: x
                return getattr(lax, name)

        device_step.lax = Lax()
    elif fault != "none":
        raise SystemExit(f"unknown fault {fault}")


if __name__ == "__main__":
    from benchmark import run
    from tests.benchmark import tiny

    root, seed, fault = sys.argv[1:4]
    cell = sys.argv[4] if len(sys.argv) > 4 else tiny.CELL
    sys.exit(run.main(["--workload", cell, "--seed", seed,
                       "--seconds", "1", "--trace", "0"],
                      require_tpu=False, root=root,
                      before_train=lambda probe: plant(fault)))
