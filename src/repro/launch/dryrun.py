import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^^ must precede every other import: jax locks the device count at first init
"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this builds ShapeDtypeStruct stand-ins (weak-type-correct,
sharded, zero allocation) for params / optimizer state / caches / batch,
jits the right step function against the production mesh, runs
``.lower().compile()``, and records:

  * ``memory_analysis()``  — per-device bytes (proves it fits 16 GB v5e HBM)
  * ``cost_analysis()``    — per-device HLO FLOPs / bytes for §Roofline
  * collective bytes       — parsed from the post-SPMD HLO text, per
                             collective kind, wire-byte convention:
                             all-gather/reduce-scatter (g−1)/g·size,
                             all-reduce 2(g−1)/g·size, all-to-all
                             (g−1)/g·size, collective-permute size.

Shape kinds map to programs:  train_* → ``train_step`` (loss+grads+AdamW);
prefill_* → ``prefill``; decode_* / long_* → ``serve_step`` (one token
against a seq_len KV cache).  On the multi-pod mesh, serving programs run
the DeServe pipeline (pod = stage axis); training folds pod into DP.

Results land in ``experiments/dryrun/<arch>__<shape>__<mesh>.json``;
``benchmarks.bench_roofline`` turns them into the roofline table.

Usage:
  python -m repro.launch.dryrun --arch yi-9b --shape decode_32k --mesh multi
  python -m repro.launch.dryrun --all [--timeout 900]
"""

import argparse
import dataclasses
import json
import re
import subprocess
import sys
import time
import traceback
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.config import MULTI_POD, SHAPES, SINGLE_POD, get_arch, list_archs
from repro.core import pipeline as pipe_lib
from repro.distributed import sharding as shard_lib
from repro.launch.mesh import make_production_mesh
from repro.models import model as model_lib
from repro.models.common import Runtime
from repro.training import optimizer as opt_lib
from repro.training.train_loop import make_train_step

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")

# TPU v5e hardware constants (per chip) for §Roofline
HW = {"peak_flops": 197e12, "hbm_bw": 819e9, "ici_bw": 50e9}

DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
               "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
               "pred": 1, "c64": 8, "c128": 16}

_COLL_RE = re.compile(
    r"=\s+(\w+)\[([\d,]*)\][^ ]*\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")
_PAIRS_RE = re.compile(r"source_target_pairs=\{")


# --variant transforms for SPerf hillclimb iterations; "" = baseline
VARIANTS = {
    "": {},
    "zero3": dict(train_style="zero3", sequence_parallel=False),
    "blockpair": dict(causal_scheme="blockpair"),
    "int8kv": dict(kv_dtype="int8"),
    "nb8": dict(),                       # serve: 8 microbatches (see below)
    "nb8_int8": dict(kv_dtype="int8"),   # combined serve hillclimb
    "zero3_accum2": dict(train_style="zero3", sequence_parallel=False),
    "zero3_blockpair": dict(train_style="zero3", sequence_parallel=False,
                            causal_scheme="blockpair"),
    "rounds8": dict(kv_dtype="int8"),    # multi-round circular decode, R=8
    # the beyond-paper optimized configuration (per shape kind):
    #   train -> ZeRO-3 weight-gathered DP over all 256 intra-pod chips
    #   serve -> int8 KV cache + 8 in-flight microbatches on the pipeline
    "opt": dict(),
}


def runtime_for(kind: str, variant: str = "") -> Runtime:
    rt = Runtime(param_dtype=jnp.bfloat16, compute_dtype=jnp.bfloat16,
                 remat=(kind == "train"), vocab_chunk=512,
                 sequence_parallel=(kind == "train"),
                 moe_chunk=65536,
                 q_chunk=512, kv_chunk=512)
    kw = dict(VARIANTS[variant])
    if variant == "opt":
        kw = (dict(train_style="zero3", sequence_parallel=False)
              if kind == "train" else dict(kv_dtype="int8"))
        kw["causal_scheme"] = "blockpair"    # exact causal FLOPs (SPerf D)
    kw = {k: v for k, v in kw.items() if hasattr(rt, k)}
    return rt.replace(**kw) if kw else rt


def serve_pipeline_config(shape, n_stages: int = 2, variant: str = ""):
    gb = shape.global_batch
    cap = 8 if variant in ("nb8", "nb8_int8", "opt") else 4
    n_mb = min(cap, gb) if gb >= n_stages else 1
    while gb % n_mb:
        n_mb -= 1
    # prefer a 16-divisible microbatch so activations shard over "data"
    # (a replicated (mb, 32k, D) prefill queue is GBs per chip)
    while n_mb > 1 and (gb // n_mb) % 16 != 0 and gb % (n_mb - 1) == 0:
        n_mb -= 1
    if (gb // n_mb) % 16 != 0 and gb >= 16 * n_stages:
        n_mb = gb // 16
    return pipe_lib.PipelineConfig(n_stages=n_stages, n_microbatches=n_mb,
                                   mb_size=gb // n_mb)


# ---------------------------------------------------------------------------
# ShapeDtypeStruct construction
# ---------------------------------------------------------------------------


def _sds(tree, specs, mesh):
    return jax.tree.map(
        lambda t, s: jax.ShapeDtypeStruct(t.shape, t.dtype,
                                          sharding=NamedSharding(mesh, s)),
        tree, specs)


def batch_inputs(cfg, shape, *, include_labels: bool):
    """Abstract input dict for one arch × shape."""
    B, S = shape.global_batch, shape.seq_len
    out = {}
    if cfg.frontend == "audio_frames":
        out["frames"] = jax.ShapeDtypeStruct((B, S, cfg.d_model),
                                             jnp.bfloat16)
        label_len = S
    elif cfg.frontend == "vision_patches":
        Pk = cfg.num_patch_tokens
        st = max(8, S - Pk)
        out["tokens"] = jax.ShapeDtypeStruct((B, st), jnp.int32)
        out["patches"] = jax.ShapeDtypeStruct((B, Pk, cfg.d_model),
                                              jnp.bfloat16)
        label_len = st
    else:
        out["tokens"] = jax.ShapeDtypeStruct((B, S), jnp.int32)
        label_len = S
    if include_labels:
        out["labels"] = jax.ShapeDtypeStruct((B, label_len), jnp.int32)
    return out


def build_cell(arch: str, shape_name: str, mesh_name: str,
               variant: str = ""):
    """Returns (fn, args_sds, meta) ready for jit(...).lower(*args)."""
    cfg = get_arch(arch)
    shape = SHAPES[shape_name]
    multi = mesh_name == "multi_pod"
    mesh = make_production_mesh(multi_pod=multi)
    rt = runtime_for(shape.kind, variant)

    if shape.kind == "train":
        # bf16 moments for the MoE giants (918M params/chip at 256 chips —
        # fp32 moments alone are 7.3 GB); dense archs keep fp32
        ocfg = opt_lib.AdamWConfig(
            moment_dtype=jnp.bfloat16 if cfg.moe is not None
            else jnp.float32)
        params = jax.eval_shape(
            lambda: model_lib.init_params(cfg, jax.random.PRNGKey(0), rt))
        opt_state = jax.eval_shape(lambda: opt_lib.init(ocfg, params))
        # gradient accumulation for the MoE giants: global batch unchanged,
        # per-microbatch activation/dispatch state 4x smaller
        accum = 4 if cfg.moe is not None else 1
        if variant == "zero3_accum2":
            accum = max(accum, 2)
        sub = dataclasses.replace(shape, global_batch=shape.global_batch
                                  // accum)
        batch = batch_inputs(cfg, sub, include_labels=True)
        bspecs = shard_lib.batch_specs(batch, mesh)
        if accum > 1:
            batch = {k: jax.ShapeDtypeStruct((accum,) + v.shape, v.dtype)
                     for k, v in batch.items()}
            bspecs = {k: P(*((None,) + tuple(sp)))
                      for k, sp in bspecs.items()}
        pspecs = shard_lib.param_specs(params, cfg, mesh, fsdp=True)
        ospecs = shard_lib.opt_state_specs(pspecs)
        step = make_train_step(cfg, rt, ocfg, accum_steps=accum)
        args = (_sds(params, pspecs, mesh), _sds(opt_state, ospecs, mesh),
                _sds(batch, bspecs, mesh))
        donate = (0, 1)
        return step, args, mesh, donate

    capacity = shape.seq_len
    # single-pod serving of the giants: TP-only weights exceed HBM
    # (qwen3-moe: 235e9*2/16 = 29 GB/chip), so shard the second weight dim
    # over "data" too and let XLA gather per layer — the roofline then shows
    # the collective cost, which is precisely the paper's argument for
    # pipelining across pods instead.
    serve_2d = cfg.param_count() * 2 / 16 > 10e9
    if shape.kind == "prefill":
        if not multi:
            params = jax.eval_shape(
                lambda: model_lib.init_params(cfg, jax.random.PRNGKey(0), rt))
            inputs = batch_inputs(cfg, shape, include_labels=False)
            pspecs = shard_lib.param_specs(params, cfg, mesh, fsdp=serve_2d)
            bspecs = shard_lib.batch_specs(inputs, mesh)
            fn = lambda p, b: model_lib.prefill(p, b, cfg, rt, capacity)
            args = (_sds(params, pspecs, mesh), _sds(inputs, bspecs, mesh))
            return fn, args, mesh, ()
        pcfg = serve_pipeline_config(shape, variant=variant)
        params = jax.eval_shape(
            lambda: model_lib.init_params(cfg, jax.random.PRNGKey(0), rt))
        flat_inputs = batch_inputs(cfg, shape, include_labels=False)
        inputs = {k: jax.ShapeDtypeStruct(
            (pcfg.n_microbatches, pcfg.mb_size) + v.shape[1:], v.dtype)
            for k, v in flat_inputs.items()}
        caches = jax.eval_shape(
            lambda: pipe_lib.init_pipeline_caches(cfg, pcfg, capacity, rt))
        pspecs = shard_lib.param_specs(params, cfg, mesh, fsdp=False)
        cspecs = shard_lib.cache_specs(caches, cfg, mesh, pipeline=True)
        ispecs = {k: P(None, "data", *([None] * (v.ndim - 2)))
                  if pcfg.mb_size % 16 == 0 else
                  P(*([None] * v.ndim)) for k, v in inputs.items()}
        fn = lambda p, b, c: pipe_lib.pipeline_prefill(p, b, c, cfg, rt, pcfg)
        args = (_sds(params, pspecs, mesh), _sds(inputs, ispecs, mesh),
                _sds(caches, cspecs, mesh))
        return fn, args, mesh, (2,)

    # decode / long-context decode: serve_step
    B = shape.global_batch
    params = jax.eval_shape(
        lambda: model_lib.init_params(cfg, jax.random.PRNGKey(0), rt))
    pspecs = shard_lib.param_specs(params, cfg, mesh,
                                   fsdp=serve_2d and not multi)
    if not multi:
        caches = jax.eval_shape(
            lambda: model_lib.init_caches(cfg, B, capacity, rt))
        cspecs = shard_lib.cache_specs(caches, cfg, mesh)
        tokens = jax.ShapeDtypeStruct((B,), jnp.int32)
        cur = jax.ShapeDtypeStruct((B,), jnp.int32)
        tspec = P("data") if B % 16 == 0 else P(None)
        fn = lambda p, t, c, cp: model_lib.decode_step(p, t, c, cp, cfg, rt)
        args = (_sds(params, pspecs, mesh),
                jax.ShapeDtypeStruct(tokens.shape, tokens.dtype,
                                     sharding=NamedSharding(mesh, tspec)),
                _sds(caches, cspecs, mesh),
                jax.ShapeDtypeStruct(cur.shape, cur.dtype,
                                     sharding=NamedSharding(mesh, tspec)))
        return fn, args, mesh, (2,)
    pcfg = serve_pipeline_config(shape, variant=variant)
    caches = jax.eval_shape(
        lambda: pipe_lib.init_pipeline_caches(cfg, pcfg, capacity, rt))
    cspecs = shard_lib.cache_specs(caches, cfg, mesh, pipeline=True)
    tspec = P(None, "data") if pcfg.mb_size % 16 == 0 else P(None, None)
    tok = jax.ShapeDtypeStruct((pcfg.n_microbatches, pcfg.mb_size), jnp.int32,
                               sharding=NamedSharding(mesh, tspec))
    cur = jax.ShapeDtypeStruct((pcfg.n_microbatches, pcfg.mb_size), jnp.int32,
                               sharding=NamedSharding(mesh, tspec))
    if variant == "rounds8" and pcfg.n_microbatches >= 2:
        fn = lambda p, t, c, cp: pipe_lib.pipeline_decode_rounds(
            p, t, c, cp, cfg, rt, pcfg, rounds=8)
    else:
        fn = lambda p, t, c, cp: pipe_lib.pipeline_decode_step(p, t, c, cp,
                                                               cfg, rt, pcfg)
    args = (_sds(params, pspecs, mesh), tok, _sds(caches, cspecs, mesh), cur)
    return fn, args, mesh, (2,)


# ---------------------------------------------------------------------------
# Collective parsing
# ---------------------------------------------------------------------------


def collective_bytes(hlo_text: str) -> dict:
    """Per-device wire bytes per collective kind, from post-SPMD HLO."""
    out = {"all-reduce": 0.0, "all-gather": 0.0, "reduce-scatter": 0.0,
           "all-to-all": 0.0, "collective-permute": 0.0}
    counts = {k: 0 for k in out}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        dtype, dims, kind = m.group(1), m.group(2), m.group(3)
        if dtype not in DTYPE_BYTES:
            continue
        size = DTYPE_BYTES[dtype]
        for d in dims.split(","):
            if d:
                size *= int(d)
        g = _group_size(line)
        frac = (g - 1) / g if g > 1 else 0.0
        if kind == "all-reduce":
            wire = 2.0 * frac * size
        elif kind == "collective-permute":
            wire = float(size)
        else:
            wire = frac * size
        out[kind] += wire
        counts[kind] += 1
    out["total"] = sum(out.values())
    out["counts"] = counts
    return out


def _group_size(line: str) -> int:
    m = _GROUPS_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_LIST_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    if _PAIRS_RE.search(line):
        return 2
    return 1


def pod_boundary_bytes(hlo_text: str, n_devices: int) -> float:
    """Bytes crossing the pod (slow-link) boundary: collective-permutes whose
    source/target differ by half the device count (the pod stride), plus
    any collective whose replica group spans both pods."""
    half = n_devices // 2
    total = 0.0
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        dtype, dims, kind = m.group(1), m.group(2), m.group(3)
        if dtype not in DTYPE_BYTES:
            continue
        size = DTYPE_BYTES[dtype]
        for d in dims.split(","):
            if d:
                size *= int(d)
        if kind == "collective-permute":
            pairs = re.search(r"source_target_pairs=\{([^}]*)\}", line)
            if pairs:
                cross = re.findall(r"\{(\d+),(\d+)\}", "{" + pairs.group(1) + "}")
                if any(abs(int(a) - int(b)) >= half for a, b in cross):
                    total += size
        else:
            m2 = _GROUPS_RE.search(line)
            if m2 and int(m2.group(2)) > half:
                total += size
    return total


# ---------------------------------------------------------------------------
# Cell runner
# ---------------------------------------------------------------------------


def run_cell(arch: str, shape_name: str, mesh_name: str,
             out_dir: Optional[str] = None, variant: str = "") -> dict:
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "variant": variant, "ok": False, "skipped": False}
    cfg = get_arch(arch)
    shape = SHAPES[shape_name]
    if shape_name == "long_500k" and not cfg.is_subquadratic():
        rec.update(skipped=True, ok=True,
                   reason="pure full-attention arch: 500k KV decode is "
                          "intentionally out of scope (see DESIGN.md)")
        _write(rec, out_dir)
        return rec
    t0 = time.time()
    try:
        fn, args, mesh, donate = build_cell(arch, shape_name, mesh_name,
                                            variant)
        with mesh:
            jitted = jax.jit(fn, donate_argnums=donate)
            lowered = jitted.lower(*args)
            t1 = time.time()
            compiled = lowered.compile()
            t2 = time.time()
            ma = compiled.memory_analysis()
            ca = compiled.cost_analysis()
            txt = compiled.as_text()
        n_dev = mesh.devices.size
        coll = collective_bytes(txt)
        rec.update(
            ok=True,
            n_devices=int(n_dev),
            lower_s=round(t1 - t0, 2),
            compile_s=round(t2 - t1, 2),
            flops_per_device=float(ca.get("flops", 0.0)),
            bytes_per_device=float(ca.get("bytes accessed", 0.0)),
            memory={
                "argument_bytes": int(ma.argument_size_in_bytes),
                "output_bytes": int(ma.output_size_in_bytes),
                "temp_bytes": int(ma.temp_size_in_bytes),
                "alias_bytes": int(ma.alias_size_in_bytes),
                "code_bytes": int(ma.generated_code_size_in_bytes),
                "peak_per_device": int(ma.argument_size_in_bytes
                                       + ma.output_size_in_bytes
                                       + ma.temp_size_in_bytes
                                       - ma.alias_size_in_bytes),
            },
            collectives=coll,
            kv_dtype=runtime_for(shape.kind, rec.get("variant", "")).kv_dtype,
            pod_boundary_bytes=float(
                pod_boundary_bytes(txt, n_dev)) if mesh_name == "multi_pod"
            else 0.0,
            tokens_per_step=shape.tokens_per_step * (
                8 if rec.get("variant") == "rounds8" and
                shape.kind == "decode" else 1),
        )
        rec["roofline"] = roofline_terms(rec)
    except Exception as e:  # noqa: BLE001 — every failure is a bug report
        rec.update(ok=False, error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-2000:])
    _write(rec, out_dir)
    return rec


def roofline_terms(rec: dict) -> dict:
    """compute/memory/collective terms (seconds) per §ROOFLINE."""
    flops = rec["flops_per_device"]
    byts = rec["bytes_per_device"]
    coll = rec["collectives"]["total"]
    terms = {
        "compute_s": flops / HW["peak_flops"],
        "memory_s": byts / HW["hbm_bw"],
        "collective_s": coll / HW["ici_bw"],
    }
    dom = max(terms, key=terms.get)
    terms["dominant"] = dom
    total = max(terms["compute_s"], terms["memory_s"], terms["collective_s"])
    terms["bound_step_s"] = total
    terms["compute_fraction_of_bound"] = (
        terms["compute_s"] / total if total > 0 else 0.0)
    return terms


def _write(rec: dict, out_dir: Optional[str]) -> None:
    d = out_dir or OUT_DIR
    os.makedirs(d, exist_ok=True)
    v = f"__{rec['variant']}" if rec.get("variant") else ""
    name = f"{rec['arch']}__{rec['shape']}__{rec['mesh']}{v}.json"
    with open(os.path.join(d, name), "w") as f:
        json.dump(rec, f, indent=1)


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape", choices=sorted(SHAPES))
    ap.add_argument("--mesh", choices=["single", "multi"], default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--timeout", type=int, default=1200)
    ap.add_argument("--out", default=None)
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--variant", default="", choices=sorted(VARIANTS))
    args = ap.parse_args()

    if args.all:
        return _run_all(args)

    mesh_name = "multi_pod" if args.mesh == "multi" else "single_pod"
    rec = run_cell(args.arch, args.shape, mesh_name, args.out, args.variant)
    dump = {k: v for k, v in rec.items() if k != "traceback"}
    print(json.dumps(dump, indent=1))
    return 0 if rec["ok"] else 1


def _run_all(args) -> int:
    archs = [a for a in list_archs() if a != "llama3-70b"] + ["llama3-70b"]
    failures = []
    for mesh in ("single", "multi"):
        for arch in archs:
            for shape in SHAPES:
                mesh_name = "multi_pod" if mesh == "multi" else "single_pod"
                out = args.out or OUT_DIR
                v = f"__{args.variant}" if args.variant else ""
                path = os.path.join(out,
                                    f"{arch}__{shape}__{mesh_name}{v}.json")
                if args.skip_existing and os.path.exists(path):
                    with open(path) as f:
                        if json.load(f).get("ok"):
                            continue
                cmd = [sys.executable, "-m", "repro.launch.dryrun",
                       "--arch", arch, "--shape", shape, "--mesh", mesh]
                if args.variant:
                    cmd += ["--variant", args.variant]
                if args.out:
                    cmd += ["--out", args.out]
                t0 = time.time()
                try:
                    r = subprocess.run(cmd, capture_output=True, text=True,
                                       timeout=args.timeout)
                    ok = r.returncode == 0
                except subprocess.TimeoutExpired:
                    ok = False
                    _write({"arch": arch, "shape": shape, "mesh": mesh_name,
                            "ok": False, "skipped": False,
                            "error": f"compile timeout > {args.timeout}s"},
                           args.out)
                status = "ok" if ok else "FAIL"
                print(f"[{status}] {arch} × {shape} × {mesh} "
                      f"({time.time()-t0:.0f}s)", flush=True)
                if not ok:
                    failures.append((arch, shape, mesh))
    if failures:
        print(f"{len(failures)} failures: {failures}")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
