"""deep_vision_tpu — a TPU-native (JAX/Flax/pjit) computer-vision framework.

Re-designed from scratch with the capabilities of the `deep-vision` reference
model zoo (classification / detection / pose / GANs), built TPU-first:

- NHWC layouts, bfloat16 matmul/conv policy, static shapes everywhere.
- One unified :class:`~deep_vision_tpu.core.trainer.Trainer` replacing the
  reference's three trainer generations (PyTorch imperative, TF1-Keras,
  TF2 MirroredStrategy custom loops).
- Parallelism via ``jax.sharding.Mesh`` + ``jit`` (GSPMD): data parallelism is
  input sharding over the ``data`` mesh axis with XLA-inserted collectives over
  ICI, not NCCL wrappers.
- Host-side numpy input pipelines with double-buffered ``device_put`` prefetch
  replacing torch DataLoader / tf.data.
"""

import time

#: ``time.monotonic`` as the package was first imported: where the launch
#: record (obs/launch.py) ends ``outside`` and begins ``import``
IMPORTED_AT = time.monotonic()

__version__ = "0.1.0"
