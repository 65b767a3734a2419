"""Scan-over-layers machinery.

Stacks of homogeneous blocks are scanned so the HLO stays O(1) in depth —
this is what makes 126-layer × 512-device programs compile on a CPU host.
Heterogeneous architectures are sequences of homogeneous *stages*.

``scan_stack(fn, stacked_params, h, xs=None)`` where
``fn(layer_params, h, x_l) -> (h', y_l)``; ``xs``/``ys`` carry per-layer
state (KV caches in decode, collected caches in prefill).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def remat_policy(name: str):
    if name == "none":
        return None
    if name == "dots":
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    return jax.checkpoint_policies.nothing_saveable  # "full"


def scan_stack(fn, stacked_params, h, xs=None, *, remat: str = "full"):
    """Scan `fn` over the leading (layer) axis of `stacked_params`.

    fn(layer_params, h, x_l) -> (h_new, y_l);  y_l may be None.
    Returns (h_final, ys) with ys stacked on a leading layer axis.
    """

    def body(carry, scanned):
        lp, x_l = scanned
        h_new, y_l = fn(lp, carry, x_l)
        return h_new, y_l

    n = jax.tree.leaves(stacked_params)[0].shape[0]
    if remat != "none":
        # prevent_cse=False is safe (and faster) under a scan loop: the
        # body is not unrolled, so CSE cannot defeat remat
        body = jax.checkpoint(body, policy=remat_policy(remat),
                              prevent_cse=False)
    if xs is None:
        xs_t = (stacked_params, _nones(n))
    else:
        xs_t = (stacked_params, xs)
    h_final, ys = jax.lax.scan(body, h, xs_t)
    return h_final, ys


def _nones(n):
    return jnp.zeros((n, 0), jnp.float32)  # zero-width placeholder, scans cheaply
