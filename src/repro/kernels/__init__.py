"""Pallas TPU kernels for the compute hot spots.

Each kernel package has three files:
  kernel.py -- ``pl.pallas_call`` with explicit BlockSpec VMEM tiling
  ops.py    -- the jit'd public wrapper (dispatch, layout, interpret fallback)
  ref.py    -- the pure-jnp oracle the kernel is validated against

| kernel          | hot spot                                               |
|-----------------|--------------------------------------------------------|
| flash_attention | 32k-prefill quadratic attention (online softmax)       |
| ssd             | Mamba-2 intra-chunk block (decay . CB^T . X fused)     |
| gru             | AIP/policy GRU recurrence (fused gates per step)       |
| gae             | GAE-lambda reverse scan over rollouts                  |

``gru`` and ``gae`` are TRAINABLE (``jax.custom_vjp`` with Pallas
backward-scan kernels) and sit on the DIALS hot path: the
``use_kernels: auto|on|off`` knob on ``AIPConfig`` / ``PolicyConfig`` /
``PPOConfig`` (driven globally by ``DIALSConfig``) routes
``aip_sequence``/``train_aip``, ``policy_sequence``, and the inner-step
GAE through them — resolved once per call site by
``repro.kernels.dispatch``.

On TPU the kernels compile through Mosaic (``tests/test_tpu_compile.py``
compiles the DIALS ones for a described v5e chip); on any other backend
they execute with ``interpret=True``. ``layout.batch_major`` keeps a
``vmap``'d agent axis out of the GAE blocks' tiled last two dims; the
GRU launches fold it into an agent axis of their own, so that one grid
step advances a block of agents.
"""
from repro.kernels import dispatch, flash_attention, gae, gru, ssd  # noqa: F401
