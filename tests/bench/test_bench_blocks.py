"""Blocks are files found by name: a configuration names its block, a
missing one is refused, and a second block added as files alone (its
module, a configuration, a cell) resolves and runs its reference."""

import json

import numpy as np
import pytest

import bench_cpu
from bench import model, reference, spec

# a residual stack of SwiGLU MLPs without attention: enough of a block to
# show that one is found by name (the program serves no such model)
MLP_BLOCK = '''
import functools

from bench.reference import mm, rms


def sizes(conf):
    return dict(d=int(conf["hidden_size"]), f=int(conf["intermediate_size"]),
                v=int(conf["vocab_size"]),
                layers=int(conf["num_hidden_layers"]),
                eps=float(conf["rms_norm_eps"]))


def param_shapes(conf):
    s = sizes(conf)
    d, f, n = s["d"], s["f"], s["layers"]
    layer = {"ln": ((n, d), None), "wg": ((n, d, f), d),
             "wu": ((n, d, f), d), "wd": ((n, f, d), f)}
    return {"embed": {"tok": ((s["v"], d), d), "untok": ((s["v"], d), d)},
            "final_norm": ((d,), None), "scan": [layer], "tail": []}


@functools.lru_cache(maxsize=None)
def _layer_fn(eps, kind):
    import jax

    def layer(x, w):
        a = rms(x, w["ln"], eps)
        return x + mm(jax.nn.silu(mm(a, w["wg"], kind)) *
                      mm(a, w["wu"], kind), w["wd"], kind)
    return jax.jit(layer)


def reference_layers(conf, params, x, kind, row):
    s = sizes(conf)
    layer = _layer_fn(s["eps"], kind)
    for i in range(s["layers"]):
        x = layer(x, {k: row(v, i) for k, v in params["scan"][0].items()})
    return x


def decode_token_flops(conf, ctx):
    s = sizes(conf)
    return s["layers"] * 6 * s["d"] * s["f"] + 2 * s["d"] * s["v"]


def prefill_flops(conf, start, stop, last):
    s = sizes(conf)
    return (stop - start) * s["layers"] * 6 * s["d"] * s["f"] + \\
        (2 * s["d"] * s["v"] if last else 0)


def paged_decode_calls(conf, lens, layers):
    return []
'''

MLP_CONF = {"name": "tiny-mlp", "source": "test", "block": "mlp_only",
            "hidden_size": 64, "intermediate_size": 96, "vocab_size": 50,
            "num_hidden_layers": 2, "rms_norm_eps": 1e-6,
            "torch_dtype": "float32"}


def test_a_missing_block_is_refused(tmp_path):
    root = bench_cpu.make_root(tmp_path)
    path = tmp_path / "bench" / "configs" / "tiny-gqa.json"
    conf = json.load(open(path))
    json.dump(dict(conf, block="no_such_block"), open(path, "w"))
    with pytest.raises(spec.SpecError, match="blocks/no_such_block.py"):
        spec.resolve("tiny.prefill", root)
    del conf["block"]
    json.dump(conf, open(path, "w"))
    with pytest.raises(spec.SpecError, match="names no block"):
        spec.resolve("tiny.prefill", root)


def test_a_new_block_is_added_as_files(tmp_path):
    root = bench_cpu.make_root(tmp_path, cells={"mlp.cell": False})
    (tmp_path / "bench" / "blocks" / "mlp_only.py").write_text(MLP_BLOCK)
    json.dump(MLP_CONF, open(tmp_path / "bench" / "configs" /
                             "tiny-mlp.json", "w"))
    b = json.load(open(tmp_path / "BENCHMARK.json"))
    b["configs"].append({"name": "tiny-mlp", "source": "test",
                         "file": "bench/configs/tiny-mlp.json",
                         "reduced": [], "why": "test"})
    b["workloads"][0]["config"] = "tiny-mlp"
    json.dump(b, open(tmp_path / "BENCHMARK.json", "w"))

    cell = spec.resolve("mlp.cell", root)
    assert cell.block.paged_decode_calls(MLP_CONF, [3], 2) == []
    params = model.make_params(cell.block, MLP_CONF, 2 ** 40 + 9)
    toks = np.array([3, 1, 4, 1, 5, 9, 2, 6], np.int32)
    got = np.asarray(reference.Reference(cell.block, MLP_CONF, params)
                     .logits(toks, [7]))[0]

    p = {k: np.asarray(v, np.float64) for k, v in
         params["scan"][0].items()}

    def norm(x, g):
        return x / np.sqrt(np.mean(x * x, -1, keepdims=True) + 1e-6) * \
            (1 + g)
    x = np.asarray(params["embed"]["tok"], np.float64)[toks]
    for i in range(2):
        a = norm(x, p["ln"][i])
        g = a @ p["wg"][i]
        x = x + (g / (1 + np.exp(-g)) * (a @ p["wu"][i])) @ p["wd"][i]
    want = norm(x[7], np.asarray(params["final_norm"], np.float64)) @ \
        np.asarray(params["embed"]["untok"], np.float64).T
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
