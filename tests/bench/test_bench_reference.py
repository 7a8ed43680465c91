"""The benchmark's float32 reference, reached through the dense-GQA block
(``bench/blocks/dense_gqa.py``), against the program's own prefill at a
tiny float32 size, and the benchmark's weights against the program's
parameter layout."""

import jax.numpy as jnp
import numpy as np
import pytest

import bench_cpu  # noqa: F401  (the checkout root on sys.path)
from bench import model, reference, spec

BLOCK = spec.load_block(spec.ROOT, "dense_gqa")

TINY32 = {
    "name": "tiny-gqa-f32", "source": "test", "block": "dense_gqa",
    "hidden_size": 256,
    "intermediate_size": 384, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 64, "num_hidden_layers": 3,
    "vocab_size": 300, "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
    "max_position_embeddings": 1024, "tie_word_embeddings": False,
    "torch_dtype": "float32"}


@pytest.fixture(scope="module")
def params():
    return model.make_params(BLOCK, TINY32, 2 ** 33 + 5)


def test_layout_matches_the_program(params):
    model.check_layout(BLOCK, TINY32, params)


def test_weights_follow_the_seed(params):
    again = model.make_params(BLOCK, TINY32, 2 ** 33 + 5)
    other = model.make_params(BLOCK, TINY32, 5)          # equal low 32 bits
    a = np.asarray(params["scan"][0]["wq"])
    assert np.array_equal(a, np.asarray(again["scan"][0]["wq"]))
    assert not np.array_equal(a, np.asarray(other["scan"][0]["wq"]))


@pytest.mark.parametrize("n", [5, 37, 300])
def test_reference_matches_program_prefill(params, n):
    from repro.models import model as model_lib
    cfg = BLOCK.program_config(TINY32)
    rt = model.program_runtime(TINY32)
    toks = np.random.default_rng(n).integers(1, 300, n).astype(np.int32)
    want, _ = model_lib.prefill(params, {"tokens": jnp.asarray(toks)[None]},
                                cfg, rt, n)
    got = reference.Reference(BLOCK, TINY32, params).logits(toks, [n - 1])
    np.testing.assert_allclose(np.asarray(got)[0], np.asarray(want)[0],
                               rtol=2e-4, atol=2e-4)


def test_controls_differ_from_the_reference(params):
    toks = np.arange(1, 40, dtype=np.int32)
    ref = reference.Reference(BLOCK, TINY32, params)
    base = np.asarray(ref.logits(toks, np.arange(39)))
    for kind in ("int8", "fp8"):
        c = np.asarray(ref.logits(toks, np.arange(39), kind=kind))
        err = np.abs(c - base).max()
        assert 0 < err < 0.5 * np.abs(base).max(), kind
