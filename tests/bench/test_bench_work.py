"""Operation and byte counts of the dense-GQA block against hand-computed
shapes, the roofline, the table of peaks, and the refusals: an unknown
device kind, a device that is not a TPU, and a checkout without the
program."""

import json
import os
import shutil
import subprocess
import sys

import pytest

import bench_cpu  # noqa: F401  (the checkout root on sys.path)
from bench import peaks, spec, work

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

BLOCK = spec.load_block(spec.ROOT, "dense_gqa")

# d 8, f 16, 2 q heads and 1 kv head of 4, 3 layers, vocab 10
SMALL = {"hidden_size": 8, "intermediate_size": 16,
         "num_attention_heads": 2, "num_key_value_heads": 1, "head_dim": 4,
         "num_hidden_layers": 3, "vocab_size": 10, "rope_theta": 1e4,
         "rms_norm_eps": 1e-6, "torch_dtype": "bfloat16"}
# per layer: q 8*8, k 8*4, v 8*4, o 8*8, mlp 3*8*16
PER_LAYER = 64 + 32 + 32 + 64 + 384


def test_matmul_params_per_layer():
    assert BLOCK.matmul_params_per_layer(SMALL) == PER_LAYER


def test_decode_token_flops():
    # 3 layers x (2 x 576 + 4 x 2 heads x 4 x ctx 5) + logits 2 x 8 x 10
    assert BLOCK.decode_token_flops(SMALL, 5) == \
        3 * (2 * PER_LAYER + 4 * 2 * 4 * 5) + 160


def test_prefill_flops():
    # positions 2, 3, 4 attend over 3, 4, 5 positions: 12 in all
    assert BLOCK.prefill_flops(SMALL, 2, 5, last=False) == \
        3 * (2 * PER_LAYER * 3 + 4 * 2 * 4 * 12)
    assert BLOCK.prefill_flops(SMALL, 2, 5, last=True) == \
        BLOCK.prefill_flops(SMALL, 2, 5, last=False) + 160
    # a whole prompt equals the sum of its chunks
    whole = BLOCK.prefill_flops(SMALL, 0, 7, last=True)
    parts = BLOCK.prefill_flops(SMALL, 0, 3, last=False) + \
        BLOCK.prefill_flops(SMALL, 3, 7, last=True)
    assert whole == parts


def test_paged_decode_call():
    flops, nbytes = BLOCK.paged_decode_call(SMALL, [5, 11])
    assert flops == 4 * 2 * 4 * 16
    # keys and values of 16 tokens, 1 kv head of 4, bf16; q and out of
    # 2 rows x 2 heads x 4
    assert nbytes == 2 * 16 * 1 * 4 * 2 + 2 * 2 * 2 * 4 * 2


def test_roofline_seconds():
    p = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.roofline_seconds(200, 10, p) == (2.0, "compute")
    assert work.roofline_seconds(100, 50, p) == (5.0, "memory")


def test_peak_table():
    v5e = peaks.peaks_for("TPU v5 lite")
    assert v5e["bf16_flops"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert "source" in v5e
    with pytest.raises(peaks.UnknownDevice, match="TPU v9"):
        peaks.peaks_for("TPU v9")


def test_a_cpu_is_refused():
    from bench import run, spec
    cell = spec.resolve("yi9b-24L.decode-resident")
    with pytest.raises(run.NoChip, match="needs a TPU"):
        run.devices_for(cell, require_tpu=True)


def _bench(cwd, env_extra=None, pythonpath=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    if pythonpath:
        env["PYTHONPATH"] = pythonpath
    return subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload",
         "yi9b-24L.decode-resident", "--seed", str(2 ** 31 + 3),
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_cli_without_a_tpu_prints_no_result():
    out = _bench(ROOT)
    assert out.returncode == 2, out.stderr[-2000:]
    assert out.stdout.strip() == ""
    assert "needs a TPU" in out.stderr


def test_cli_without_the_program_prints_no_result(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's paths."""
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in bench["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    out = _bench(str(tmp_path))
    assert out.returncode != 0
    assert out.stdout.strip() == ""


def test_paged_decode_calls():
    """One call a layer, each over the same rows."""
    one = BLOCK.paged_decode_call(SMALL, [5, 11])
    assert BLOCK.paged_decode_calls(SMALL, [5, 11], 3) == [one + (3,)]
    assert BLOCK.paged_decode_calls(SMALL, [5, 11], 0) == []
