"""Backbones: the core of a policy or influence-predictor (AIP) network,
between its shared MLP trunk and its heads, one file per ``kind`` that a
configuration's ``policy`` or ``aip`` object names. ``get(kind)`` finds
``backbones/<kind>.py``; a kind with no file raises a KeyError that
names the file to add. Each file holds the core's plain reference and
its counts:

- ``init(key, din, net) -> (entries, dout)``: the core's float32
  parameters as entries of the network's parameter tree, and the width
  it hands the heads;
- ``hidden0(net, batch, dtype)``: the state carried from step to step,
  zeros of shape ``batch + (...)``;
- ``step(params, x, h, net) -> (y, h')``: one step of rows ``x``
  (..., din), ``params`` the whole network's tree;
- ``sequence(params, xs, h0, resets, net) -> ys``: (B, T, din), the
  state zeroed before each step whose ``resets`` (B, T) is 1;
- ``fwd_flops(din, net) -> (flops, dout)``: matmul FLOPs of one step of
  one row (2 per multiply-add);
- ``kernel_ops(net, rows, steps, backward) -> [(flops, bytes)]``: the
  kernel operations one pass over ``rows`` sequences of ``steps`` steps
  launches, and where ``backward`` those of its backward; the kernels
  are named ``<kind>_fwd`` / ``<kind>_bwd`` in the program, so
  ``harness.trace`` finds their time by the same kind.

``net`` is the configuration's network object with every key it has,
plus the sizes the environment fixes (``obs_dim`` and ``n_actions``,
or ``in_dim`` and ``n_sources``).
"""
from ... import catalog


def get(kind: str):
    return catalog.module(__name__, kind)
