"""Declarative weight specs.

A layer declares its weights once as a pytree of ``WSpec``; the same tree
drives initialization, abstract evaluation (ShapeDtypeStruct for the
dry-run) and PartitionSpec derivation (via common.sharding.tree_pspecs).
A leaf the model reads only as a default-precision matmul operand says
so (``mxu_operand``), so that serving can hold it as the MXU reads it
(``round_mxu_operands``).
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np


@dataclass(frozen=True)
class WSpec:
    shape: tuple[int, ...]
    axes: tuple[Any, ...]            # logical axis names (or None), len == ndim
    init: str = "normal"             # normal | zeros | ones | embed | small
    scale: float | None = None       # stddev override for "normal"
    dtype: Any = None                # None -> param_dtype at init time
    mxu_operand: bool = False        # read only as a default-precision matmul operand

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def _is_wspec(x) -> bool:
    return isinstance(x, WSpec)


def _std(ws: WSpec) -> float:
    if ws.scale is not None:
        return ws.scale
    if ws.init == "embed":
        return 1.0
    if ws.init == "small":
        return 0.02
    # fan-in normal
    fan_in = int(np.prod(ws.shape[:-1])) or 1
    return 1.0 / float(np.sqrt(fan_in))


def init_leaf(key, ws: WSpec, param_dtype) -> jax.Array:
    dtype = ws.dtype or param_dtype
    if ws.init == "zeros":
        return jnp.zeros(ws.shape, dtype)
    if ws.init == "ones":
        return jnp.ones(ws.shape, dtype)
    return (jax.random.normal(key, ws.shape, jnp.float32) * _std(ws)).astype(dtype)


def init_tree(key, spec_tree, param_dtype=jnp.float32):
    leaves, treedef = jax.tree.flatten(spec_tree, is_leaf=_is_wspec)
    out = [
        init_leaf(jax.random.fold_in(key, i), ws, param_dtype)
        for i, ws in enumerate(leaves)
    ]
    return jax.tree.unflatten(treedef, out)


def abstract_tree(spec_tree, param_dtype=jnp.float32, shardings=None):
    """ShapeDtypeStruct pytree; if `shardings` pytree given, attach them."""

    def one(ws, sh=None):
        dtype = ws.dtype or param_dtype
        if sh is not None:
            return jax.ShapeDtypeStruct(ws.shape, dtype, sharding=sh)
        return jax.ShapeDtypeStruct(ws.shape, dtype)

    if shardings is None:
        return jax.tree.map(one, spec_tree, is_leaf=_is_wspec)
    return jax.tree.map(one, spec_tree, shardings, is_leaf=_is_wspec)


def stack_specs(spec_tree, n: int):
    """Prepend a scanned-layers dimension (logical axis "layers")."""
    return jax.tree.map(
        lambda ws: replace(ws, shape=(n, *ws.shape), axes=("layers", *ws.axes)),
        spec_tree,
        is_leaf=_is_wspec,
    )


def spec_param_count(spec_tree) -> int:
    return sum(
        int(np.prod(ws.shape))
        for ws in jax.tree.leaves(spec_tree, is_leaf=_is_wspec)
    )


def spec_param_bytes(spec_tree, param_dtype=jnp.bfloat16) -> int:
    total = 0
    for ws in jax.tree.leaves(spec_tree, is_leaf=_is_wspec):
        dt = ws.dtype or param_dtype
        total += int(np.prod(ws.shape)) * jnp.dtype(dt).itemsize
    return total


#: ``jax_default_matmul_precision`` values at which a float32 matmul on
#: the TPU is one bfloat16 MXU pass
_ONE_BF16_PASS = (None, "default", "bfloat16", "fastest")


def mxu_rounds_f32(platform: str, precision) -> bool:
    """Whether a default-precision matmul on ``platform`` reads a float32
    operand as its bfloat16 rounding: on the TPU, unless the default
    matmul precision asks for more than one pass; never on the CPU,
    whose float32 matmul is exact."""
    return platform == "tpu" and precision in _ONE_BF16_PASS


_to_bf16 = jax.jit(lambda xs: [x.astype(jnp.bfloat16) for x in xs])


def round_mxu_operands(params, spec_tree, *, platform: str, precision):
    """``params`` with every float32 leaf whose spec is an ``mxu_operand``
    held as its bfloat16 rounding, where the platform's default matmul
    rounds float32 operands so (``mxu_rounds_f32``): the same values the
    MXU reads, made once in one jitted call instead of in every step.
    Every other leaf is passed through as the same array.  Returns
    (params, bytes of the bfloat16 copies)."""
    if not mxu_rounds_f32(platform, precision):
        return params, 0
    picked = jax.tree.map(
        lambda ws, x: ws.mxu_operand and x.dtype == jnp.float32,
        spec_tree, params, is_leaf=_is_wspec)
    leaves, treedef = jax.tree.flatten(params)
    idx = [i for i, p in enumerate(jax.tree.leaves(picked)) if p]
    if not idx:
        return params, 0
    for i, x in zip(idx, _to_bf16([leaves[i] for i in idx])):
        leaves[i] = x
    return (jax.tree.unflatten(treedef, leaves),
            sum(leaves[i].size * leaves[i].dtype.itemsize for i in idx))
