"""Reads the first three steps off the object the window then drives.

The trainer builds ONE compiled chunk and one state and calls
``chunk(state, data)`` until it is stopped. The probe sits around that call:
it is the same function object, the same state and the same on-device feed
in the first three calls as in the window's. Around those three it copies
the parameters to the host before the first call (the call donates them),
takes each call's training loss, Adam's ``m`` after the first call (the
first gradient is m / (1 - b1): its leaves' norms, and the leaves themselves
copied to the host for the reference to be held against) and the norm of
every leaf's change after the third. From the fourth call on it is one
comparison and a reference kept to the newest state, which the check reads
against the checkpoint that the drain writes.

It changes nothing the step computes and adds no device work to the window.
"""

from __future__ import annotations

import threading

import jax
import jax.numpy as jnp

ADAM_B1 = 0.9
STEPS = 3


@jax.jit
def _norm(x):
    return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))


@jax.jit
def _diff_norm(a, b):
    return jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)
                                       - b.astype(jnp.float32))))


class FirstStepsProbe:
    """Install before ``train()``; read ``result`` once ``done`` is set.
    ``leaf_names`` is the cell's family's: it names the parameters' leaves
    as that family's reference names its own."""

    def __init__(self, leaf_names, builders=("make_device_train_step",
                                             "make_device_dp_train_step")):
        self.leaf_names = leaf_names
        self.builders = builders
        self.calls = 0
        self.done = threading.Event()
        self.result: dict | None = None
        self.last_state = None
        self._start = None
        self._losses = []
        self._m_norms = None
        self.first_m = None
        self._originals = {}

    # -- installation ----------------------------------------------------
    def install(self):
        from distributed_tensorflow_tpu.training import device_step

        for name in self.builders:
            original = getattr(device_step, name)
            self._originals[name] = original
            setattr(device_step, name, self._wrapping(original))
        return self

    def uninstall(self):
        from distributed_tensorflow_tpu.training import device_step

        for name, original in self._originals.items():
            setattr(device_step, name, original)
        self._originals = {}

    def _wrapping(self, builder):
        def build(*args, **kwargs):
            if kwargs.get("chunk", 1) != 1:
                raise ValueError("the first-steps probe needs one step a "
                                 "call (--device_chunk 1)")
            return self.wrap(builder(*args, **kwargs))

        return build

    # -- the call ----------------------------------------------------------
    def wrap(self, fn):
        def call(state, data):
            if self.calls >= STEPS:
                out = fn(state, data)
                self.last_state = out[0]
                return out
            if self.calls == 0:
                self._start = jax.device_get(state.params)
            out = fn(state, data)
            self.calls += 1
            new_state, metrics = out
            self._losses.append(metrics["loss"])
            if self.calls == 1:
                first_m = jax.tree.leaves(new_state.opt_state["m"])
                self._m_norms = [_norm(x) for x in first_m]
                # the first gradient itself, kept on the host until the
                # reference has its own to hold against it
                self.first_m = jax.device_get(first_m)
            if self.calls == STEPS:
                self._finish(new_state)
            self.last_state = new_state
            return out

        return call

    def _finish(self, state):
        names = self.leaf_names(state.params)
        change = {}
        starts = jax.tree.leaves(self._start)
        self._start = None
        for name, new in zip(names, jax.tree.leaves(state.params)):
            old = jax.device_put(starts.pop(0), new.sharding)
            change[name] = float(_diff_norm(new, old))
            del old
        self.result = {
            "losses": [float(x) for x in self._losses],
            "grad_norms": {n: float(m) / (1 - ADAM_B1)
                           for n, m in zip(names, self._m_norms)},
            "change_norms": change,
        }
        self._losses, self._m_norms = [], None
        self.done.set()

    def first_gradient(self):
        """The first gradient's leaves on the host, as the optimizer got
        them; handed over once (it is as large as the parameters)."""
        leaves, self.first_m = self.first_m, None
        return [m / (1 - ADAM_B1) for m in leaves]

    def release(self):
        """Drop the newest state so that its device memory is freed."""
        self.last_state = None
