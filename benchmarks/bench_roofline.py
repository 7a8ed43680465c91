"""Roofline analysis (deliverable g): read the dry-run artifacts and emit
the per-(arch × shape × mesh) three-term roofline table.

Terms (seconds, per step, per chip):
    compute    = HLO_FLOPs / peak_FLOPs          (197 bf16 TFLOP/s v5e)
    memory     = HLO_bytes / HBM_bw              (819 GB/s)
    collective = collective_bytes / ICI_bw       (~50 GB/s/link)

Cross-check column: MODEL_FLOPS / HLO_FLOPs with MODEL_FLOPS = 6·N_active·T
(train) or 2·N_active·T (serve).  Ratios < 1 mean the compiled program does
extra work (remat recompute, MoE capacity padding, masked-attention
overcount); ratios > 1 mean XLA's counter *under-reports* (CPU fusions,
nested while loops — see the MoE note in models/moe.py), in which case the
analytic bound is the honest compute term and the table uses
``compute_eff = max(HLO, analytic)``.
"""

import glob
import json
import os

from repro.config import SHAPES, get_arch

HW = {"peak_flops": 197e12, "hbm_bw": 819e9, "ici_bw": 50e9}
HBM_BYTES = 16e9      # v5e per-chip HBM

DRYRUN_DIR = os.path.join(os.path.dirname(__file__), "..", "experiments",
                          "dryrun")
OUT_MD = os.path.join(os.path.dirname(__file__), "..", "experiments",
                      "roofline.md")


def memory_floor_bytes(arch: str, shape_name: str, n_dev: int,
                       kv_bytes_per_elem: int = 2) -> float:
    """Analytic lower bound on HBM traffic per chip per step: every live
    parameter is read once (weight-stationary decode reads them all), the
    KV/state cache is read (+1 token written), and train adds grad+moment
    writes.  Used as a floor under XLA's (CPU-lossy) bytes counter."""
    cfg = get_arch(arch)
    shape = SHAPES[shape_name]
    p_bytes = cfg.active_param_count() * 2
    if shape.kind == "train":
        # params read fwd+bwd + grads written + adam m/v read+write (fp32)
        traffic = cfg.param_count() * (2 * 3 + 4 + 4 * 4)
        acts = shape.seq_len * shape.global_batch * cfg.d_model * 2 * \
            cfg.num_layers * 2
        return (traffic + acts) / n_dev
    kv = cfg.kv_bytes_per_token(kv_bytes_per_elem) * shape.seq_len * \
        shape.global_batch
    if shape.kind == "prefill":
        return (p_bytes + kv) / n_dev
    return (p_bytes * 1.0 + kv) / n_dev          # decode reads all KV


def model_flops(arch: str, shape_name: str) -> float:
    cfg = get_arch(arch)
    shape = SHAPES[shape_name]
    n = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.seq_len * shape.global_batch
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.seq_len * shape.global_batch
        return 2.0 * n * tokens
    return 2.0 * n * shape.global_batch          # decode: one token/seq


def load_cells():
    cells = []
    for path in sorted(glob.glob(os.path.join(DRYRUN_DIR, "*.json"))):
        with open(path) as f:
            cells.append(json.load(f))
    return cells


def analyse(rec: dict) -> dict:
    n_dev = rec.get("n_devices", 256)
    mf = model_flops(rec["arch"], rec["shape"])
    hlo = rec["flops_per_device"] * n_dev
    ratio = mf / hlo if hlo > 0 else float("inf")
    flops_eff = max(rec["flops_per_device"], mf / n_dev)
    compute_eff = flops_eff / HW["peak_flops"]
    kv_b = 1 if rec.get("kv_dtype") == "int8" else 2
    mem_floor = memory_floor_bytes(rec["arch"], rec["shape"], n_dev, kv_b)
    terms = {
        "compute_s": compute_eff,
        "memory_s": max(rec["bytes_per_device"], mem_floor) / HW["hbm_bw"],
        "collective_s": rec["collectives"]["total"] / HW["ici_bw"],
    }
    dom = max(terms, key=terms.get)
    bound = terms[dom]
    useful = (mf / n_dev) / HW["peak_flops"]
    return {
        **terms,
        "dominant": dom,
        "bound_s": bound,
        "model_hlo_ratio": ratio,
        # the score: fraction of the bound spent on *useful* model FLOPs
        "roofline_fraction": useful / bound if bound > 0 else 0.0,
        "peak_gb": rec["memory"]["peak_per_device"] / 1e9,
        "fits_hbm": rec["memory"]["peak_per_device"] <= HBM_BYTES,
        "tok_per_s_bound": rec.get("tokens_per_step", 0) / bound
        if bound > 0 else 0.0,
    }


def run(quick: bool = False):
    all_cells = load_cells()
    by_variant = {}
    for c in all_cells:
        by_variant.setdefault(c.get("variant", ""), []).append(c)
    rows = []
    for variant in sorted(by_variant):
        label = variant or "baseline"
        if variant not in ("", "opt"):
            continue                      # hillclimb singles live in SPerf
        rows.extend(_run_table(by_variant[variant], label))
    _kernel_roofline(rows, quick)
    return rows


def _kernel_roofline(rows, quick: bool):
    """Measured per-kernel achieved-vs-peak fractions (PR 8 satellite).

    Unlike the dry-run table above (analytic v5e numbers from compiled
    HLO), these rows *time* the attention implementation that actually
    serves on this backend — the Pallas kernels on TPU, the XLA oracles
    on CPU (interpret-mode Pallas timings would measure the Python
    evaluator, not the machine).  Peaks are calibrated in-process on the
    same host: a large f32 matmul for FLOP/s, a large read+write map for
    bytes/s.  Decode attention is scored against the bandwidth peak (its
    arithmetic intensity is ~1 FLOP/byte), prefill flash attention
    against the FLOP peak.  Rows land in BENCH_throughput.json and
    check_regression.py surfaces them as informational (non-gated)
    cells."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ref
    from repro.kernels.flash_attention import flash_attention_pallas
    from repro.kernels.paged_attention import paged_decode_attention as _pl

    def best_s(fn, *args, iters=None):
        iters = iters or (5 if quick else 10)
        out = fn(*args)
        jax.block_until_ready(out)
        best = float("inf")
        for _ in range(iters):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            best = min(best, time.perf_counter() - t0)
        return best

    # -- host peak calibration (same process, same thread pool) -----------
    n = 768 if quick else 1024
    a = jnp.zeros((n, n), jnp.float32)
    peak_flops = 2.0 * n ** 3 / best_s(jax.jit(jnp.dot), a, a)
    big = jnp.zeros((32 * 1024 * 1024,), jnp.float32)   # 128 MB stream
    t_bw = best_s(jax.jit(lambda x: x + 1.0), big)
    peak_bw = 2.0 * big.nbytes / t_bw                   # read + write

    on_tpu = jax.default_backend() == "tpu"
    rng = np.random.RandomState(0)
    print("\n== Kernel roofline (measured on this host; "
          f"{'Pallas' if on_tpu else 'XLA oracle'} path) ==")
    print(f"   calibrated peaks: {peak_flops / 1e9:.1f} GFLOP/s, "
          f"{peak_bw / 1e9:.1f} GB/s")

    # -- paged decode attention: bandwidth-bound (reads the whole KV) -----
    b, hq, hk, dh = 8, 8, 2, 128
    page, maxp = 16, 8 if quick else 16
    npool = 1 + b * maxp
    q = jnp.asarray(rng.randn(b, hq, dh), jnp.float32)
    kp = jnp.asarray(rng.randn(npool, page, hk, dh), jnp.float32)
    vp = jnp.asarray(rng.randn(npool, page, hk, dh), jnp.float32)
    pt = jnp.arange(1, 1 + b * maxp, dtype=jnp.int32).reshape(b, maxp)
    lens = jnp.full((b,), page * maxp, jnp.int32)
    if on_tpu:
        f = jax.jit(lambda *xs: _pl(*xs, interpret=False))
    else:
        f = jax.jit(lambda *xs: ref.paged_decode_attention_ref(*xs))
    t = best_s(f, q, kp, vp, pt, lens)
    kv_bytes = 2 * b * page * maxp * hk * dh * 4        # k+v, f32
    bw = kv_bytes / t
    rows.append({"bench": "kernel_roofline", "kernel": "paged_decode",
                 "t_us": t * 1e6, "achieved": bw / 1e9,
                 "peak": peak_bw / 1e9, "unit": "GB/s",
                 "frac": bw / peak_bw})
    print(f"   paged_decode   {t * 1e6:9.1f} us  {bw / 1e9:7.1f} GB/s "
          f"({bw / peak_bw:6.1%} of stream peak)")

    # -- flash prefill attention: compute-bound (causal QK^T + PV) --------
    s = 256 if quick else 512
    bq = 2
    qf = jnp.asarray(rng.randn(bq, s, hq, dh), jnp.float32)
    kf = jnp.asarray(rng.randn(bq, s, hq, dh), jnp.float32)
    vf = jnp.asarray(rng.randn(bq, s, hq, dh), jnp.float32)
    if on_tpu:
        g = jax.jit(lambda *xs: flash_attention_pallas(
            *xs, causal=True, interpret=False))
    else:
        g = jax.jit(lambda *xs: ref.flash_attention_ref(*xs, causal=True))
    t = best_s(g, qf, kf, vf)
    flops = 2.0 * bq * hq * s * s * dh                  # 4·B·H·S²·D / 2
    fl = flops / t
    rows.append({"bench": "kernel_roofline", "kernel": "flash_prefill",
                 "t_us": t * 1e6, "achieved": fl / 1e9,
                 "peak": peak_flops / 1e9, "unit": "GFLOP/s",
                 "frac": fl / peak_flops})
    print(f"   flash_prefill  {t * 1e6:9.1f} us  {fl / 1e9:7.1f} GFLOP/s "
          f"({fl / peak_flops:6.1%} of matmul peak)")
    return rows


def _run_table(cells, label):
    rows = []
    ok = skipped = failed = 0
    lines = ["| arch | shape | mesh | peak GB | fits | compute s | "
             "memory s | coll s | dominant | MF/HLO | roofline frac |",
             "|---|---|---|---|---|---|---|---|---|---|---|"]
    print(f"\n== Roofline [{label}] (per chip, per step; "
          "from the dry-run artifacts) ==")
    print(f"{'arch':25s}{'shape':13s}{'mesh':11s}{'pkGB':>6s}{'fit':>4s}"
          f"{'comp_s':>10s}{'mem_s':>10s}{'coll_s':>10s} {'dom':10s}"
          f"{'MF/HLO':>7s}{'frac':>7s}")
    for rec in cells:
        if rec.get("skipped"):
            skipped += 1
            continue
        if not rec.get("ok"):
            failed += 1
            print(f"{rec['arch']:25s}{rec['shape']:13s}{rec['mesh']:11s}"
                  f"  FAILED: {rec.get('error', '')[:60]}")
            continue
        ok += 1
        a = analyse(rec)
        dom = a["dominant"].replace("_s", "")
        print(f"{rec['arch']:25s}{rec['shape']:13s}{rec['mesh']:11s}"
              f"{a['peak_gb']:6.1f}{'y' if a['fits_hbm'] else 'N':>4s}"
              f"{a['compute_s']:10.2e}{a['memory_s']:10.2e}"
              f"{a['collective_s']:10.2e} {dom:10s}"
              f"{a['model_hlo_ratio']:7.2f}{a['roofline_fraction']:7.3f}")
        lines.append(
            f"| {rec['arch']} | {rec['shape']} | {rec['mesh']} | "
            f"{a['peak_gb']:.1f} | {'yes' if a['fits_hbm'] else 'NO'} | "
            f"{a['compute_s']:.2e} | {a['memory_s']:.2e} | "
            f"{a['collective_s']:.2e} | {dom} | "
            f"{a['model_hlo_ratio']:.2f} | {a['roofline_fraction']:.3f} |")
        rows.append({"bench": f"roofline_{label}", "arch": rec["arch"],
                     "shape": rec["shape"], "mesh": rec["mesh"], **a})
    print(f"\n   cells: {ok} compiled, {skipped} skipped "
          f"(long_500k on full-attention archs), {failed} failed")
    out = OUT_MD.replace(".md", f"_{label}.md")
    with open(out, "w") as f:
        f.write("\n".join(lines) + "\n")
    print(f"   table written to {out}")
    return rows
