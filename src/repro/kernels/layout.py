"""Operand layout at the ``pallas_call`` boundary."""
from __future__ import annotations

import jax


def batch_major(*xs):
    """Identity on values of rank >= 2. Under ``vmap`` it moves each
    operand's mapped axis to the front, so that ``pallas_call``'s
    batching rule adds the agent axis as a leading grid dimension (the
    GAE kernels). Left where it was — e.g. axis 1 of an (E, N, T) array
    vmapped with ``in_axes=1`` — that axis would land inside a block's
    last two dims, which the TPU compiler rejects. (The GRU kernels fold
    a mapped axis into their own agent axis instead; see
    ``repro.kernels.gru.kernel``.)

    It is a flatten-and-restore pair of reshapes: reshape's batching rule
    moves the mapped axis to 0, and a same-shape reshape would be elided
    at trace time. Unbatched, XLA folds the pair away."""
    out = []
    for x in xs:
        assert x.ndim >= 2, x.shape
        out.append(jax.lax.reshape(jax.lax.reshape(x, (x.size,)), x.shape))
    return out
