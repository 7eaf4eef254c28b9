"""TPU-native distributed training framework.

A ground-up JAX/XLA rebuild of the capabilities of the reference
``ellie-ba/Distributed_TensorFlow`` (a distributed deep-CNN MNIST classifier
on TensorFlow's parameter-server runtime, ``/root/reference/.idea/MNISTDist.py``),
re-designed TPU-first:

- model/ops layer: pure-JAX functional CNN / ResNet (XLA:TPU kernels, MXU)
- parallelism: synchronous data-parallel over a ``jax.sharding.Mesh``
  (``psum`` gradients over ICI) as the default mode, plus an async
  parameter-server emulation mode reproducing the reference's
  stale-gradient SGD (worker/ps roles over host-side RPC)
- orchestration: chief-led init, periodic checkpoint + auto-restore,
  cadenced logging, shared-global-step termination — the Supervisor
  semantics of the reference (``MNISTDist.py:158-193``)
- CLI surface: identical flags (``--job_name --task_index --ps_hosts
  --worker_hosts`` + model/training flags, ``MNISTDist.py:13-31``)
"""

__version__ = "0.1.0"

