"""DeepCNN with the Pallas FC path (interpret mode on CPU): parity + training."""

import jax
import jax.numpy as jnp
import numpy as np

from distributed_tensorflow_tpu.models import DeepCNN
from distributed_tensorflow_tpu.training import adam, create_train_state, make_train_step


def test_pallas_model_forward_matches_xla():
    ref = DeepCNN()
    pal = DeepCNN(use_pallas=True, pallas_interpret=True)
    params = ref.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.key(1), (8, 784)) * 0.5
    a = ref.apply(params, x)
    b = pal.apply(params, x)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5)


def test_pallas_model_trains():
    model = DeepCNN(use_pallas=True, pallas_interpret=True)
    opt = adam(1e-3)
    state = create_train_state(model, opt, seed=0)
    step_fn = make_train_step(model, opt, donate=False)
    from distributed_tensorflow_tpu.data.synthetic import synthetic_digits

    xs, labels = synthetic_digits(32, seed=0)
    batch = (jnp.asarray(xs), jax.nn.one_hot(jnp.asarray(labels), 10))
    losses = []
    for _ in range(8):
        state, m = step_fn(state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0]


def test_pallas_without_the_interpreter_fails_on_the_cpu():
    """``--pallas`` builds ``DeepCNN(use_pallas=True)`` and nothing else:
    off the TPU that is an error from Pallas itself, never a quiet switch
    to the interpreter (which only these tests ask for)."""
    import pytest

    model = DeepCNN(use_pallas=True)
    params = model.init(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="[Ii]nterpret"):
        model.apply(params, jnp.zeros((8, 784)))
