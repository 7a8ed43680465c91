"""The weights the benchmark makes for a configuration, and the check that
they have the program's parameter layout.

The weights are the benchmark's own: one jitted call builds every leaf
that the configuration's block lists (``param_shapes`` of
``bench/blocks/<block>.py``) from ``--seed``, in the served dtype, each
leaf where the cell's backend keeps it.  Nothing here knows a block.
"""

from __future__ import annotations

import math


def program_runtime(conf: dict):
    """The program's ``Runtime`` in the configuration's dtype."""
    import jax.numpy as jnp

    from repro.models.common import Runtime
    dt = jnp.dtype(conf["torch_dtype"])
    return Runtime(param_dtype=dt, compute_dtype=dt)


def prng_key(seed: int):
    """A key that tells apart every seed up to 2**64 (``PRNGKey`` keeps
    only the low 32 bits of a large seed)."""
    import jax
    seed %= 2 ** 64
    key = jax.random.PRNGKey(0)
    for part in (seed & 0xFFFFFFFF, seed >> 32):
        key = jax.random.fold_in(key, part)
    return key


GAIN_STD = 0.1


def make_params(block, conf: dict, seed: int, place=None):
    """Every weight from ``seed``: matrices N(0, 1/fan-in), norm gains
    1 + N(0, GAIN_STD**2), in the configuration's dtype.  ``place`` maps
    the tree of the leaves' shapes to their shardings (None: JAX's default
    device)."""
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(conf["torch_dtype"])
    leaves, tree = jax.tree.flatten(
        block.param_shapes(conf), is_leaf=lambda x: isinstance(x, tuple)
        and isinstance(x[0], tuple))

    def build(key):
        out = []
        for i, (shape, fan_in) in enumerate(leaves):
            k = jax.random.fold_in(key, i)
            scale = GAIN_STD if fan_in is None else 1.0 / math.sqrt(fan_in)
            out.append(jax.random.normal(k, shape, dt) * jnp.asarray(scale,
                                                                     dt))
        return jax.tree.unflatten(tree, out)

    key = prng_key(seed)
    shardings = None if place is None else place(jax.eval_shape(build, key))
    return jax.jit(build, out_shardings=shardings)(key)


def check_layout(block, conf: dict, params) -> None:
    """The benchmark's weights have the structure, shapes and dtypes of the
    program's own parameters; raises where the program's layout moved."""
    import jax

    from repro.models import model as model_lib
    want = jax.eval_shape(lambda k: model_lib.init_params(
        block.program_config(conf), k, program_runtime(conf)),
        jax.random.PRNGKey(0))
    got = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                       params)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype) for a, b in
            zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise ValueError("the program's parameter layout differs from the "
                         f"benchmark's weights: {want} vs {got}")
