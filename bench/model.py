"""A configuration file as the program runs it, and the weights the
benchmark makes for it.

The weights are the benchmark's own: one jitted call on the device builds
every leaf from ``--seed`` in the served dtype, in the parameter layout of
the program's dense decoder (``{"embed": {"tok", "untok"}, "final_norm",
"scan": [one period, each leaf stacked over the layers], "tail": []}``).
The program stores each RMSNorm gain as its offset from 1; the reference
reads the same leaves with the same meaning.
"""

from __future__ import annotations

import math

def sizes(conf: dict) -> dict:
    """Plain sizes of a dense-GQA configuration file."""
    h = int(conf["num_attention_heads"])
    d = int(conf["hidden_size"])
    return dict(
        d=d, f=int(conf["intermediate_size"]), h=h,
        hk=int(conf["num_key_value_heads"]),
        dh=int(conf.get("head_dim") or d // h),
        v=int(conf["vocab_size"]), layers=int(conf["num_hidden_layers"]),
        theta=float(conf["rope_theta"]), eps=float(conf["rms_norm_eps"]),
        dtype=conf["torch_dtype"])


def program_config(conf: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.config import ModelConfig
    s = sizes(conf)
    if conf.get("tie_word_embeddings"):
        raise ValueError("the reference serves untied heads only")
    return ModelConfig(
        name=conf["name"], family="dense", num_layers=s["layers"],
        d_model=s["d"], num_heads=s["h"], num_kv_heads=s["hk"],
        head_dim=s["dh"], d_ff=s["f"], vocab_size=s["v"],
        block_pattern=("attn",), rope_theta=s["theta"], norm_eps=s["eps"],
        max_position_embeddings=int(conf["max_position_embeddings"]),
        source=conf["source"])


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


def param_shapes(conf: dict) -> dict:
    """(shape, fan-in or None for a norm gain) of every leaf."""
    s = sizes(conf)
    d, f, v, n = s["d"], s["f"], s["v"], s["layers"]
    q, kv = s["h"] * s["dh"], s["hk"] * s["dh"]
    layer = {
        "ln1": ((n, d), None), "wq": ((n, d, q), d), "wk": ((n, d, kv), d),
        "wv": ((n, d, kv), d), "wo": ((n, q, d), q), "ln2": ((n, d), None),
        "wg": ((n, d, f), d), "wu": ((n, d, f), d), "wd": ((n, f, d), f),
    }
    return {"embed": {"tok": ((v, d), d), "untok": ((v, d), d)},
            "final_norm": ((d,), None), "scan": [layer], "tail": []}


GAIN_STD = 0.1


def make_params(conf: dict, seed: int, device=None):
    """Every weight, on the device, from ``seed``: matrices N(0, 1/fan-in),
    norm gains 1 + N(0, GAIN_STD**2), in the configuration's dtype."""
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(conf["torch_dtype"])
    shapes = param_shapes(conf)
    leaves, tree = jax.tree.flatten(
        shapes, is_leaf=lambda x: isinstance(x, tuple) and
        isinstance(x[0], tuple))

    def build(key):
        out = []
        for i, (shape, fan_in) in enumerate(leaves):
            k = jax.random.fold_in(key, i)
            scale = GAIN_STD if fan_in is None else 1.0 / math.sqrt(fan_in)
            out.append(jax.random.normal(k, shape, dt) * jnp.asarray(scale,
                                                                     dt))
        return jax.tree.unflatten(tree, out)

    sharding = None if device is None else \
        jax.sharding.SingleDeviceSharding(device)
    return jax.jit(build, out_shardings=sharding)(prng_key(seed))


def check_layout(conf: dict, params) -> None:
    """The benchmark's weights have the structure, shapes and dtypes of the
    program's own parameters; raises where the program's layout moved."""
    import jax

    from repro.models import model as model_lib
    want = jax.eval_shape(lambda k: model_lib.init_params(
        program_config(conf), k, program_runtime(conf)),
        jax.random.PRNGKey(0))
    got = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                       params)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype) for a, b in
            zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise ValueError("the program's parameter layout differs from the "
                         f"benchmark's weights: {want} vs {got}")


def matmul_params_per_layer(conf: dict) -> int:
    s = sizes(conf)
    q, kv = s["h"] * s["dh"], s["hk"] * s["dh"]
    return s["d"] * (q + 2 * kv) + q * s["d"] + 3 * s["d"] * s["f"]

