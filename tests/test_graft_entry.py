"""Driver entry points: entry() compiles, dryrun_multichip runs on 8 devices."""

import importlib.util
import os

import jax
import numpy as np

_spec = importlib.util.spec_from_file_location(
    "__graft_entry__",
    os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                 "__graft_entry__.py"),
)
graft = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(graft)


def test_entry_jits():
    fn, args = graft.entry()
    out = jax.jit(fn)(*args)
    assert out.shape == (8, 10)
    assert np.isfinite(np.asarray(out)).all()


def test_dryrun_multichip_8():
    graft.dryrun_multichip(8)


def test_dryrun_multichip_2():
    graft.dryrun_multichip(2)


def test_dryrun_multichip_raises_without_the_devices():
    """No fallback to a made-up platform: more devices than JAX reports
    is an error that says how many there are."""
    import pytest

    with pytest.raises(RuntimeError, match="needs 16 devices and JAX "
                                           "reports 8"):
        graft.dryrun_multichip(16)
