"""Tiny cells for the CPU: the real metrics and the real traffic shapes,
at widths the CPU and the Pallas interpreter run in seconds.  Importing
it puts the checkout's root, where ``bench`` lives, on ``sys.path``."""

from __future__ import annotations

import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# the smallest dense-GQA shapes that still route decode through the Pallas
# paged kernel (head dim 128, page size 16)
TINY = {
    "name": "tiny-gqa", "source": "test", "block": "dense_gqa",
    "hidden_size": 256,
    "intermediate_size": 512, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 128, "num_hidden_layers": 2,
    "vocab_size": 512, "rope_theta": 10000.0, "rms_norm_eps": 1e-6,
    "max_position_embeddings": 256, "tie_word_embeddings": False,
    "torch_dtype": "bfloat16"}

TRAFFIC = {
    "loop": "closed",
    "prompt_tokens": {"dist": "lognormal", "median": 24, "sigma": 0.5,
                      "min": 8, "max": 48},
    "output_tokens": {"dist": "uniform", "min": 4, "max": 12},
    "greedy_share": 0.5, "sampled": {"temperature": 0.8, "top_p": 0.95},
    "ramp_min_steps": 2}


DEFAULT_LIMITS = {"gap": 0.05, "logprob": 0.05}


def committed_limits() -> dict:
    """The limits of every cell that ``BENCHMARK.json`` names."""
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    return {w["name"]: json.load(open(os.path.join(
        ROOT, "bench", "cells", f"{w['name']}.json")))["check"]["limits"]
        for w in bench["workloads"]}


def engine(offload: bool) -> dict:
    return {"backend": "local", "num_microbatches": 2, "mb_size": 2,
            "page_size": 16, "n_local_pages": 12 if offload else 24,
            "n_global_pages": 6 if offload else 0, "max_pages_per_seq": 4,
            "offload": offload, "prefill_chunk": 32,
            "max_prefill_tokens_per_tick": 64}


def make_root(tmp, *, limits=None, cells=None) -> str:
    """A checkout-like root whose BENCHMARK.json names tiny cells and
    whose metric readers and blocks are the real ones."""
    root = str(tmp)
    real = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cells = cells or {"tiny.decode-offload": True, "tiny.prefill": False}
    names = list(cells)
    os.makedirs(os.path.join(root, "bench", "configs"), exist_ok=True)
    for d in ("cells", "traffic"):
        os.makedirs(os.path.join(root, "bench", d), exist_ok=True)
    for d in ("metrics", "blocks"):
        shutil.copytree(os.path.join(ROOT, "bench", d),
                        os.path.join(root, "bench", d), dirs_exist_ok=True)
    json.dump(TINY, open(os.path.join(root, "bench", "configs",
                                      "tiny-gqa.json"), "w"))
    json.dump(TRAFFIC, open(os.path.join(root, "bench", "traffic",
                                         "short.json"), "w"))
    limits = limits or DEFAULT_LIMITS
    for name, offload in cells.items():
        json.dump({"engine": engine(offload),
                   "check": {"requests": 6, "limits": limits}},
                  open(os.path.join(root, "bench", "cells",
                                    f"{name}.json"), "w"))
    bench = {
        "command": real["command"], "paths": real["paths"],
        "run_seconds": 2,
        "configs": [{"name": "tiny-gqa", "source": "test",
                     "file": "bench/configs/tiny-gqa.json", "reduced": [],
                     "why": "test"}],
        "workloads": [{"name": n, "config": "tiny-gqa", "traffic": "short",
                       "chips": 1, "why": "test"} for n in names],
        "end_to_end": [{k: v for k, v in m.items() if k != "workloads"}
                       for m in real["end_to_end"]],
        "per_layer": [{**m, "workloads": names} for m in real["per_layer"]],
    }
    json.dump(bench, open(os.path.join(root, "BENCHMARK.json"), "w"))
    return root
