"""Inter-layer (pipeline) parallelism over the high-latency ``pod`` axis.

This is DeServe's distribution strategy (§2.3 + §4.3) mapped onto JAX SPMD:
the model's scanned periods are split into ``n_stages`` contiguous stage
slices (weights never cross the slow link), activations move stage→stage
with ``lax.ppermute`` inside a ``shard_map`` that is *manual over the pod
axis only* — data/tensor parallelism inside each pod stays automatic, so
each stage is itself a 256-chip DP×TP program.

Schedule: the §4.3 circular schedule with ``N_B`` microbatches in flight;
one call = one full pass (fill + steady + drain, ``T = N_B + N_S − 1``
ticks).  At tick ``t`` pod ``p`` works on microbatch ``t − p`` (when in
range); out-of-range ticks are pipeline bubbles — their cache writes are
masked.  The scheduler (``repro.core.scheduler``) picks ``N_B`` from the
measured stage time and link latency so that steady-state bubbles vanish;
here ``N_B`` is a static compile-time parameter, exactly as the paper's
implementation fixes it per deployment.

Stage assignment is period-aligned: ``pps = n_periods // n_stages`` scanned
periods per stage.  Leftover periods and the pattern tail run as a shared
*epilogue* — replicated across pods, TP/DP-sharded inside — after the
drained activations are returned (the return link the paper's driver also
pays).  For every assigned arch the epilogue is ≤ 2 layers (<6 % of
compute).
"""

from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ModelConfig
from repro.models import embedding as embed_lib
from repro.models import model as model_lib
from repro.models.common import Runtime, make_layer_plan, rms_norm


def _shard_map(f, *, mesh, axis_names, in_specs, out_specs):
    """``jax.shard_map`` manual over ``axis_names`` only: data/tensor axes
    inside each stage stay automatic."""
    # repro-audit: allow(retrace-jit) — trace-time only: callers wrap the tick in one outer jax.jit, so this wrapper is built once per compile, never per tick
    return jax.shard_map(f, mesh=mesh, axis_names=axis_names,
                         in_specs=in_specs, out_specs=out_specs,
                         check_vma=False)


def _wire_permute(y, n_stages: int, wire_dtype: str):
    """Ship activations one stage downstream — the wire of §4.3's ring.

    ``wire_dtype="int8"`` packs the payload per row before the permute
    (one f32 scale per row travels with it) and dequantizes on arrival,
    so the bytes crossing the slow link are the packed ones the
    transport accounting prices.  ``"fp32"`` is the identity path: one
    ppermute of the raw activation, bit-identical to the pre-codec
    pipeline.  The branch is a trace-time Python ``if`` — each
    ``wire_dtype`` is its own compiled program, never a ``lax.cond``."""
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
    if wire_dtype == "int8":
        from repro.distributed.compression import (int8_compress_rows,
                                                   int8_decompress_rows)
        q, scale = int8_compress_rows(y)
        q = jax.lax.ppermute(q, "pod", perm)
        scale = jax.lax.ppermute(scale, "pod", perm)
        return int8_decompress_rows(q, scale, y.dtype)
    return jax.lax.ppermute(y, "pod", perm)


@dataclass(frozen=True)
class PipelineConfig:
    n_stages: int
    n_microbatches: int
    mb_size: int                      # sequences per microbatch

    @property
    def global_batch(self) -> int:
        return self.n_microbatches * self.mb_size

    @property
    def n_ticks(self) -> int:
        return self.n_microbatches + self.n_stages - 1


# ---------------------------------------------------------------------------
# Parameter / cache splitting
# ---------------------------------------------------------------------------


def split_layers(cfg: ModelConfig, n_stages: int):
    """(periods_per_stage, leftover_periods).  Stage i owns scanned periods
    [i·pps, (i+1)·pps); the leftover periods + pattern tail are epilogue."""
    plan = make_layer_plan(cfg.num_layers, cfg.block_pattern)
    pps = plan.n_periods // n_stages
    leftover = plan.n_periods - pps * n_stages
    if pps == 0:
        raise ValueError(
            f"{cfg.name}: {plan.n_periods} periods cannot fill {n_stages} "
            "pipeline stages")
    return pps, leftover


def stage_mesh(n_stages: int):
    """The ``pod`` mesh over the first ``n_stages`` devices — one pipeline
    stage per device."""
    devs = jax.devices()
    if len(devs) < n_stages:
        raise RuntimeError(
            f"pipelined backend needs >= {n_stages} devices for the "
            f"pod axis, have {len(devs)} — set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n_stages} "
            "before initialising jax, or reduce --stages")
    return jax.sharding.Mesh(np.array(devs[:n_stages]), ("pod",))


def stage_shardings(cfg: ModelConfig, mesh, n_stages: int):
    """Per-stage placement of a params or cache tree, as a function from
    the tree (arrays or shapes) to its tree of shardings.

    Leaves under ``"scan"`` carry the period stack on axis 0; they are
    split over ``"pod"``, so stage ``s`` holds periods ``[s·pps,
    (s+1)·pps)`` — exactly the slice the tick functions reshape out of the
    stack, so no weight or cache byte ever crosses a stage boundary.
    Everything else (embeddings, final norm, tail layers) is replicated.
    Leftover periods (``n_periods % n_stages``) leave the stack uneven; it
    then stays replicated."""
    P = jax.sharding.PartitionSpec
    _, leftover = split_layers(cfg, n_stages)
    rep = jax.sharding.NamedSharding(mesh, P())
    split = rep if leftover else jax.sharding.NamedSharding(mesh, P("pod"))

    def place(tree: dict) -> dict:
        return {k: jax.tree.map(lambda _: split if k == "scan" else rep, v)
                for k, v in tree.items()}
    return place


def split_scan_params(params: dict, cfg: ModelConfig, n_stages: int):
    """Split stacked scan params into (stage_params, epilogue_scan_params).

    stage leaves:    (n_stages, pps, ...)   — shard dim 0 over "pod"
    epilogue leaves: (leftover, ...) or None
    """
    pps, leftover = split_layers(cfg, n_stages)

    def split_leaf(x):
        stage = x[: pps * n_stages].reshape((n_stages, pps) + x.shape[1:])
        epi = x[pps * n_stages:] if leftover else None
        return stage, epi

    stage_list, epi_list = [], []
    for pos in params["scan"]:
        s = jax.tree.map(lambda x: split_leaf(x)[0], pos)
        e = jax.tree.map(lambda x: split_leaf(x)[1], pos) if leftover else None
        stage_list.append(s)
        epi_list.append(e)
    return stage_list, (epi_list if leftover else [])


def init_pipeline_caches(cfg: ModelConfig, pcfg: PipelineConfig,
                         capacity: int, rt: Runtime) -> dict:
    """Cache pytree for the pipelined server.

    stage caches:    leaves (n_stages, n_mb, pps, mb, ...)  [pod, none, ...]
    epilogue caches: standard model cache dict over the full global batch.
    """
    plan = make_layer_plan(cfg.num_layers, cfg.block_pattern)
    pps, leftover = split_layers(cfg, pcfg.n_stages)
    stage = [
        model_lib._kind_cache(k, cfg, pcfg.mb_size, capacity, rt,
                              lead=(pcfg.n_stages, pcfg.n_microbatches, pps))
        for k in plan.period_kinds
    ]
    epi_scan = [
        model_lib._kind_cache(k, cfg, pcfg.global_batch, capacity, rt,
                              lead=(leftover,))
        for k in plan.period_kinds
    ] if leftover else []
    tail = [model_lib._kind_cache(k, cfg, pcfg.global_batch, capacity, rt)
            for k in plan.tail_kinds]
    return {"stage": stage, "epi_scan": epi_scan, "tail": tail}


# ---------------------------------------------------------------------------
# The pipelined pass (shared by decode and prefill)
# ---------------------------------------------------------------------------


def _pipeline_pass(stage_params, stage_caches, queue, positions_q, cfg, rt,
                   pcfg: PipelineConfig, mode: str):
    """Run one fill+drain pass of the circular schedule inside shard_map.

    queue:        (n_mb, mb, S, D) embedded microbatch inputs (replicated
                  w.r.t. pod; DP/TP-sharded inside).
    positions_q:  (n_mb, [3,] mb, S) per-microbatch positions.
    Returns (drained (n_mb, mb, S, D), new_stage_caches).
    """
    n_s, n_mb = pcfg.n_stages, pcfg.n_microbatches
    pps, _ = split_layers(cfg, n_s)
    plan = make_layer_plan(cfg.num_layers, cfg.block_pattern)

    def body(local_params, local_caches, queue, positions_q):
        # local_params leaves: (1, pps, ...); local_caches: (1, n_mb, pps, ...)
        local_params = [jax.tree.map(lambda x: x[0], p) for p in local_params]
        local_caches = [jax.tree.map(lambda x: x[0], c) for c in local_caches]
        pod = jax.lax.axis_index("pod")
        is_last = pod == n_s - 1

        x0 = queue[0] * jnp.where(pod == 0, 1.0, 0.0).astype(queue.dtype)

        def tick(carry, t):
            x, caches, outs = carry
            mb_id = t - pod
            active = (mb_id >= 0) & (mb_id < n_mb)
            mb_c = jnp.clip(mb_id, 0, n_mb - 1)

            mb_caches = [jax.tree.map(
                lambda l: jax.lax.dynamic_index_in_dim(l, mb_c, 0,
                                                       keepdims=False), c)
                for c in caches]
            if positions_q.ndim == 4:          # (n_mb, 3, mb, S) m-rope
                pos = jax.lax.dynamic_index_in_dim(positions_q, mb_c, 0,
                                                   keepdims=False)
            else:
                pos = jax.lax.dynamic_index_in_dim(positions_q, mb_c, 0,
                                                   keepdims=False)
            # NOTE (SPerf iteration A4, refuted): wrapping this in
            # lax.cond(active, work, identity) to skip bubble-tick compute
            # REGRESSED the memory term 41% — the conditional materialises
            # its operand tuple (the whole per-mb cache) and blocks carry
            # aliasing.  Bubble writes are masked with where() instead.
            y, new_mb_caches = model_lib.run_periods(
                local_params, x, cfg, rt, period_kinds=plan.period_kinds,
                mode=mode, scan_caches=mb_caches, positions=pos)
            # mask bubble writes, splice the microbatch's caches back
            new_caches = []
            for c_all, c_old, c_new in zip(caches, mb_caches,
                                           new_mb_caches):
                c_new = jax.tree.map(
                    lambda n, o: jnp.where(active, n, o), c_new, c_old)
                new_caches.append(jax.tree.map(
                    lambda l, n: jax.lax.dynamic_update_index_in_dim(
                        l, n.astype(l.dtype), mb_c, 0), c_all, c_new))

            # collect the drained microbatch from the last pod
            out_id = t - (n_s - 1)
            out_c = jnp.clip(out_id, 0, n_mb - 1)
            contrib = jnp.where(is_last, y, jnp.zeros_like(y))
            old_slot = jax.lax.dynamic_index_in_dim(outs, out_c, 0,
                                                    keepdims=False)
            slot = jnp.where((out_id >= 0) & (out_id < n_mb), contrib,
                             old_slot)
            outs = jax.lax.dynamic_update_index_in_dim(outs, slot, out_c, 0)

            # ship activations around the ring; pod 0 takes the next inject
            y_next = jax.lax.ppermute(
                y, "pod", [(i, (i + 1) % n_s) for i in range(n_s)])
            nxt = jnp.clip(t + 1, 0, n_mb - 1)
            inj = jax.lax.dynamic_index_in_dim(queue, nxt, 0, keepdims=False)
            x_next = jnp.where(pod == 0, inj, y_next)
            return (x_next, new_caches, outs), None

        outs0 = jnp.zeros(queue.shape, queue.dtype)
        (x, new_caches, outs), _ = jax.lax.scan(
            tick, (x0, local_caches, outs0), jnp.arange(pcfg.n_ticks))
        # the drained buffer lives on the last pod; return it to everyone
        # (this is the paper's output return link — (n_mb, mb, S, D) once per
        # pass, not per tick).  f32 psum: XLA:CPU's AllReducePromotion pass
        # aborts cloning bf16 all-reduces emitted from partial-manual
        # shard_map (dtype identical on TPU after the pass anyway).
        outs = jax.lax.psum(
            jnp.where(is_last, outs, jnp.zeros_like(outs)).astype(
                jnp.float32), "pod").astype(outs.dtype)
        new_caches = [jax.tree.map(lambda x: x[None], c) for c in new_caches]
        return outs, new_caches

    P = jax.sharding.PartitionSpec
    in_specs = (
        [jax.tree.map(lambda _: P("pod"), p) for p in stage_params],
        [jax.tree.map(lambda _: P("pod"), c) for c in stage_caches],
        P(), P(),
    )
    out_specs = (P(), [jax.tree.map(lambda _: P("pod"), c)
                       for c in stage_caches])
    fn = _shard_map(body, mesh=_ambient_mesh(), axis_names={"pod"},
                    in_specs=in_specs, out_specs=out_specs)
    return fn(stage_params, stage_caches, queue, positions_q)


def _ambient_mesh():
    """Resolve the mesh from either the ``with mesh:`` legacy context or the
    ``jax.set_mesh`` context."""
    from jax._src import mesh as mesh_lib
    m = mesh_lib.thread_resources.env.physical_mesh
    if not m.empty:
        return m
    am = jax.sharding.get_abstract_mesh()
    if am is not None and not am.empty:
        return am
    raise RuntimeError("pipeline_* must run inside a mesh context "
                       "(`with mesh:` or `jax.set_mesh(mesh)`)")


# ---------------------------------------------------------------------------
# Serving entry points (pipelined)
# ---------------------------------------------------------------------------


def _validate_tick_args(name: str, *, mesh, n_stages: int,
                        checks: dict) -> None:
    """Trace-time argument validation for the persistent tick functions.

    They run under one outer ``jax.jit``, so a mis-shaped argument
    otherwise surfaces ticks later as a cryptic shard_map/scan error —
    or not at all, as a silent per-call retrace when a host integer
    leaks into a shape.  Runs only at trace time (shapes are static),
    so steady-state ticks pay nothing.  ``checks`` maps argument name
    to ``(got_shape, want_shape)``."""
    pod = dict(mesh.shape).get("pod")
    if pod != n_stages:
        raise ValueError(
            f"{name}: mesh 'pod' axis has {pod} device(s) but "
            f"n_stages={n_stages} — the pipe needs one stage per pod "
            "device")
    for arg, (got, want) in checks.items():
        if tuple(got) != tuple(want):
            raise ValueError(
                f"{name}: {arg} has shape {tuple(got)}, want "
                f"{tuple(want)} — the backend and the tick disagree on "
                "the pipe geometry")


def _epilogue(params, epi_scan_params, x, cfg, rt, *, mode, caches,
              positions):
    """Leftover periods + pattern tail + final norm (replicated over pods)."""
    plan = make_layer_plan(cfg.num_layers, cfg.block_pattern)
    new_epi = caches["epi_scan"] if caches is not None else None
    if epi_scan_params:
        x, new_epi = model_lib.run_periods(
            epi_scan_params, x, cfg, rt, period_kinds=plan.period_kinds,
            mode=mode, scan_caches=new_epi, positions=positions)
    new_tail = []
    for i, kind in enumerate(plan.tail_kinds):
        c = caches["tail"][i] if caches is not None else None
        x, nc = model_lib.apply_layer(kind, params["tail"][i], x, cfg, rt,
                                      positions=positions, mode=mode, cache=c)
        new_tail.append(nc)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x, new_epi, new_tail


def pipeline_decode_step(params, tokens, caches, cur_pos, cfg: ModelConfig,
                         rt: Runtime, pcfg: PipelineConfig):
    """One pipelined decode token for every microbatch.

    tokens (n_mb, mb) int32; cur_pos (n_mb, mb) int32 absolute positions.
    Returns (logits (n_mb, mb, V) f32, new_caches).
    """
    n_mb, mb = tokens.shape
    cd = rt.compute_dtype
    x = embed_lib.embed_tokens(params["embed"], tokens.reshape(-1), cfg, cd)
    queue = x.reshape(n_mb, mb, 1, cfg.d_model)
    positions_q = cur_pos[..., None]                       # (n_mb, mb, 1)
    if cfg.frontend == "vision_patches":
        positions_q = jnp.broadcast_to(positions_q[:, None],
                                       (n_mb, 3, mb, 1))

    stage_params, epi_scan_params = split_scan_params(params, cfg,
                                                      pcfg.n_stages)
    drained, new_stage = _pipeline_pass(
        stage_params, caches["stage"], queue, positions_q, cfg, rt, pcfg,
        mode="decode")

    xf = drained.reshape(pcfg.global_batch, 1, cfg.d_model)
    pos_flat = cur_pos.reshape(pcfg.global_batch)[:, None]
    if cfg.frontend == "vision_patches":
        from repro.models.common import text_positions3
        pos_flat = text_positions3(pos_flat)
    xf, new_epi, new_tail = _epilogue(params, epi_scan_params, xf, cfg, rt,
                                      mode="decode", caches=caches,
                                      positions=pos_flat)
    logits = embed_lib.unembed(params["embed"], xf[:, 0], cfg)
    new_caches = {"stage": new_stage, "epi_scan": new_epi, "tail": new_tail}
    return logits.reshape(n_mb, mb, -1), new_caches


def pipeline_prefill(params, inputs, caches, cfg: ModelConfig, rt: Runtime,
                     pcfg: PipelineConfig):
    """Pipelined prefill.

    ``inputs``: {"tokens": (n_mb, mb, S)} — or the stub-frontend forms
    {"frames": (n_mb, mb, S, D)} / {"tokens", "patches"} (vlm), all with the
    (n_mb, mb) microbatch layout on the leading dims.
    Returns (last_logits (n_mb, mb, V) f32, new_caches)."""
    if isinstance(inputs, jax.Array):
        inputs = {"tokens": inputs}
    n_mb, mb = next(iter(inputs.values())).shape[:2]
    flat = {k: v.reshape((n_mb * mb,) + v.shape[2:])
            for k, v in inputs.items()}
    x, positions = model_lib.embed_inputs(params, flat, cfg, rt,
                                          mode="prefill")
    S = x.shape[1]
    queue = x.reshape(n_mb, mb, S, cfg.d_model)
    if positions.ndim == 3:          # (3, B, S) m-rope
        positions_q = positions.reshape(3, n_mb, mb, S).transpose(1, 0, 2, 3)
        pos = positions[0].reshape(n_mb, mb, S)
    else:
        pos = positions.reshape(n_mb, mb, S)
        positions_q = pos

    stage_params, epi_scan_params = split_scan_params(params, cfg,
                                                      pcfg.n_stages)
    drained, new_stage = _pipeline_pass(
        stage_params, caches["stage"], queue, positions_q, cfg, rt, pcfg,
        mode="prefill")

    xf = drained.reshape(pcfg.global_batch, S, cfg.d_model)
    pos_flat = positions          # embed_inputs layout: (B, S) or (3, B, S)
    xf, new_epi, new_tail = _epilogue(params, epi_scan_params, xf, cfg, rt,
                                      mode="prefill", caches=caches,
                                      positions=pos_flat)
    logits = embed_lib.unembed(params["embed"], xf[:, -1], cfg)
    new_caches = {"stage": new_stage, "epi_scan": new_epi, "tail": new_tail}
    return logits.reshape(n_mb, mb, -1), new_caches


# ---------------------------------------------------------------------------
# Single-tick circular decode over ENGINE-format paged caches
# ---------------------------------------------------------------------------
#
# The serving engine's PipelinedBackend keeps the §4.3 circular schedule
# *persistent*: each engine tick injects one microbatch at stage 0 and
# advances every in-flight microbatch one stage.  Unlike the fixed-batch
# passes above (which own stage-major dense caches), this path runs over
# the engine's canonical paged-cache pytree — scan leaves (n_periods, ...)
# are split into per-stage slices inside the jit, so continuous batching,
# page tables, and the double-buffer offloader keep operating on the one
# host-side layout.


def pipeline_decode_tick(params, caches, act, tokens, mb_assign, pos_stage,
                         samp_keys, samp_steps, samp_temp, samp_top_k,
                         samp_top_p, drop_stage, *, cfg: ModelConfig,
                         rt: Runtime, n_stages: int, mb_size: int, mesh,
                         wire_dtype: str = "fp32",
                         sample_fast_path: bool = True):
    """Advance the persistent pipeline by one tick.

    caches:    engine-format paged caches ({"scan": [...], "tail": [...]}).
    act:       (n_stages, mb_size, 1, D) input activation per stage; row 0
               is replaced by the embedded ``tokens`` (the injection).
    tokens:    (mb_size,) int32 — last tokens of the injected microbatch.
    mb_assign: (n_stages,) int32 — microbatch id each stage processes this
               tick (-1 = bubble).  ``mb_assign[-1]`` is the draining one.
    pos_stage: (n_stages, mb_size) int32 absolute positions per stage.
    samp_*:    per-row sampling state of the *draining* microbatch —
               ``samp_keys`` (mb_size, 2) uint32 base keys, ``samp_steps``
               (mb_size,) token indices, temperature / top-k / top-p
               (mb_size,) — captured at its injection, so every request
               is sampled under its own params regardless of pipe depth.
    drop_stage: () int32 fault-injection seam — the stage whose tick is
               *lost* this tick (-1 = none).  Its microbatch's cache
               writes are masked exactly like a bubble's and, when it is
               the draining stage, the drained result is invalid: the
               caller must treat the microbatch as a lost tick and
               re-inject it (decode writes are position-keyed, so the
               retry rewrites identical KV — see serving/engine.py).
    wire_dtype: static wire codec for the inter-stage ppermute payload —
               "fp32" (identity, bit-identical) or "int8" (per-row
               quantize → permute → dequantize; see ``_wire_permute``).

    Returns (sampled tokens (mb_size,), model logprobs (mb_size,) for the
    draining microbatch — garbage when ``mb_assign[-1] < 0`` or the last
    stage was dropped —, new caches, new act).
    """
    from repro.serving import kv_cache as kvc
    from repro.serving.sampler import (fold_in_steps, sample_batched,
                                       token_logprobs)

    plan = make_layer_plan(cfg.num_layers, cfg.block_pattern)
    pps, leftover = split_layers(cfg, n_stages)
    n_scan = pps * n_stages
    cd = rt.compute_dtype
    _validate_tick_args(
        "pipeline_decode_tick", mesh=mesh, n_stages=n_stages, checks={
            "act": (act.shape, (n_stages, mb_size, 1, cfg.d_model)),
            "tokens": (tokens.shape, (mb_size,)),
            "mb_assign": (mb_assign.shape, (n_stages,)),
            "pos_stage": (pos_stage.shape, (n_stages, mb_size)),
            "samp_keys": (samp_keys.shape, (mb_size, 2)),
            "samp_steps": (samp_steps.shape, (mb_size,)),
        })

    stage_params, epi_scan_params = split_scan_params(params, cfg, n_stages)
    stage_caches = [jax.tree.map(
        lambda x: x[:n_scan].reshape((n_stages, pps) + x.shape[1:]), c)
        for c in caches["scan"]]
    epi_scan_caches = [jax.tree.map(lambda x: x[n_scan:], c)
                       for c in caches["scan"]] if leftover else []

    x_inj = embed_lib.embed_tokens(params["embed"], tokens, cfg, cd)[:, None]

    def body(stage_params_l, stage_caches_l, act_l, x_inj, mb_assign,
             pos_stage, drop_stage):
        lp = [jax.tree.map(lambda x: x[0], p) for p in stage_params_l]
        lc = [jax.tree.map(lambda x: x[0], c) for c in stage_caches_l]
        pod = jax.lax.axis_index("pod")
        is_last = pod == n_stages - 1

        x_in = jnp.where(pod == 0, x_inj, act_l[0])
        mb_id = jax.lax.dynamic_index_in_dim(mb_assign, pod, 0,
                                             keepdims=False)
        active = (mb_id >= 0) & (pod != drop_stage)
        row0 = jnp.maximum(mb_id, 0) * mb_size
        pos = jax.lax.dynamic_index_in_dim(pos_stage, pod, 0,
                                           keepdims=False)
        p1 = pos[:, None]
        if cfg.frontend == "vision_patches":
            from repro.models.common import text_positions3
            p1 = text_positions3(p1)

        # per-microbatch row views of this stage's period slice (pools
        # shared, per-slot leaves row-sliced at axis 1 after the period
        # axis — same convention the single-device backend uses)
        view = []
        for c in lc:
            shared, per = kvc._split_shared(c)
            per = jax.tree.map(lambda x: jax.lax.dynamic_slice_in_dim(
                x, row0, mb_size, axis=1), per)
            view.append({**shared, **per})
        y, new_view = model_lib.run_periods(
            lp, x_in, cfg, rt, period_kinds=plan.period_kinds,
            mode="decode", scan_caches=view, positions=p1)

        new_lc = []
        for c_old, v_old, v_new in zip(lc, view, new_view):
            v_new = jax.tree.map(lambda n, o: jnp.where(active, n, o),
                                 v_new, v_old)               # mask bubbles
            merged = {}
            for k in c_old:
                if k.endswith("_pages"):
                    merged[k] = v_new[k].astype(c_old[k].dtype)
                else:
                    merged[k] = jax.lax.dynamic_update_slice_in_dim(
                        c_old[k], v_new[k].astype(c_old[k].dtype), row0,
                        axis=1)
            new_lc.append(merged)

        # drained activation: the last stage's output, broadcast to all
        # pods (f32 psum: see the note in _pipeline_pass)
        y_out = jax.lax.psum(
            jnp.where(is_last, y, jnp.zeros_like(y)).astype(jnp.float32),
            "pod").astype(y.dtype)
        # ship activations one stage downstream for the next tick
        y_next = _wire_permute(y, n_stages, wire_dtype)
        new_lc = [jax.tree.map(lambda x: x[None], c) for c in new_lc]
        return y_out, y_next[None], new_lc

    P = jax.sharding.PartitionSpec
    in_specs = (
        [jax.tree.map(lambda _: P("pod"), p) for p in stage_params],
        [jax.tree.map(lambda _: P("pod"), c) for c in stage_caches],
        P("pod"), P(), P(), P(), P(),
    )
    out_specs = (P(), P("pod"),
                 [jax.tree.map(lambda _: P("pod"), c) for c in stage_caches])
    fn = _shard_map(body, mesh=mesh, axis_names={"pod"},
                    in_specs=in_specs, out_specs=out_specs)
    y_out, new_act, new_stage = fn(stage_params, stage_caches, act, x_inj,
                                   mb_assign, pos_stage,
                                   jnp.asarray(drop_stage, jnp.int32))

    # epilogue + sampling for the draining microbatch (replicated — this is
    # the paper's return link: (mb,) token ids per tick, not activations)
    out_mb = mb_assign[n_stages - 1]
    valid = (out_mb >= 0) & (jnp.asarray(drop_stage) != n_stages - 1)
    row0 = jnp.maximum(out_mb, 0) * mb_size
    pos_d = pos_stage[n_stages - 1]
    p1 = pos_d[:, None]
    if cfg.frontend == "vision_patches":
        from repro.models.common import text_positions3
        p1 = text_positions3(p1)
    epi_full = {"scan": epi_scan_caches, "tail": caches["tail"]}
    epi_view = kvc.slot_view(epi_full, row0, mb_size)
    xf, new_epi_scan, new_tail = _epilogue(
        params, epi_scan_params, y_out, cfg, rt, mode="decode",
        caches={"epi_scan": epi_view["scan"], "tail": epi_view["tail"]},
        positions=p1)
    logits = embed_lib.unembed(params["embed"], xf[:, 0], cfg)
    toks = sample_batched(logits, fold_in_steps(samp_keys, samp_steps),
                          samp_temp, samp_top_k, samp_top_p,
                          fast_path=sample_fast_path)
    lps = token_logprobs(logits, toks)

    new_epi_view = {"scan": new_epi_scan or [], "tail": new_tail}
    new_epi_view = jax.tree.map(lambda n, o: jnp.where(valid, n, o),
                                new_epi_view, epi_view)      # mask bubbles
    epi_merged = kvc.slot_merge(epi_full, new_epi_view, row0)

    new_scan = []
    for i in range(len(caches["scan"])):
        st = jax.tree.map(lambda x: x.reshape((n_scan,) + x.shape[2:]),
                          new_stage[i])
        if leftover:
            st = jax.tree.map(lambda a, b: jnp.concatenate([a, b], axis=0),
                              st, epi_merged["scan"][i])
        new_scan.append(st)
    new_caches = {"scan": new_scan, "tail": epi_merged["tail"]}
    return toks, lps, new_caches, new_act


# ---------------------------------------------------------------------------
# Single-tick chunked prefill over ENGINE-format paged caches
# ---------------------------------------------------------------------------


def pipeline_prefill_chunk_tick(params, caches, act, tokens, offs_stage,
                                valid_stage, tables_stage, lasts,
                                drop_stage, *, cfg: ModelConfig, rt: Runtime,
                                n_stages: int, mesh,
                                wire_dtype: str = "fp32"):
    """Advance the persistent *prefill* pipe by one tick.

    The serving engine's ``PipelinedBackend`` keeps a second shift register
    for prompt chunks: each engine tick injects (at most) one chunk at
    stage 0 and advances every in-flight chunk one stage, exactly like
    ``pipeline_decode_tick`` — so a prefill chunk overlaps the in-flight
    decode microbatches instead of pausing them.

    caches:       engine-format paged caches (every layer paged — the
                  engine gates ring/recurrent archs to exact prefill).
    act:          (n_stages, R, C, D) chunk activation per stage input.
    tokens:       (R, C) int32 — the chunk injected at stage 0 this tick.
    offs_stage:   (n_stages, R) int32 prefilled-token offsets per stage.
    valid_stage:  (n_stages, R) int32 real-token counts (0 = bubble row or
                  bubble stage — every cache write is dropped).
    tables_stage: (n_stages, R, P) int32 per-row page-table rows (the
                  device-wide table keeps prefilling slots parked).
    lasts:        (R,) int32 within-chunk final-token index of the
                  *draining* chunk.
    drop_stage:   () int32 fault-injection seam: the stage whose tick is
                  lost this tick (-1 = none).  Its chunk's valid counts
                  are zeroed, so every cache write at that stage is
                  dropped; the caller re-injects the lost chunk (prompt-KV
                  writes are offset-keyed, so the retry rewrites identical
                  pages — see serving/engine.py).

    Returns (logits (R, V) for the draining chunk — garbage when no chunk
    drains or the last stage was dropped —, new caches, new act).
    """
    pps, leftover = split_layers(cfg, n_stages)
    n_scan = pps * n_stages
    plan = make_layer_plan(cfg.num_layers, cfg.block_pattern)
    cd = rt.compute_dtype
    R, C = tokens.shape
    _validate_tick_args(
        "pipeline_prefill_chunk_tick", mesh=mesh, n_stages=n_stages,
        checks={
            "act": (act.shape, (n_stages, R, C, cfg.d_model)),
            "offs_stage": (offs_stage.shape, (n_stages, R)),
            "valid_stage": (valid_stage.shape, (n_stages, R)),
            "tables_stage": (tables_stage.shape[:2], (n_stages, R)),
            "lasts": (lasts.shape, (R,)),
        })
    # the fault seam: a dropped stage becomes a bubble stage — n_valid 0
    # masks every one of its cache writes through the chunk recurrences
    valid_stage = jnp.where(
        jnp.arange(n_stages)[:, None] == jnp.asarray(drop_stage), 0,
        valid_stage)

    stage_params, epi_scan_params = split_scan_params(params, cfg, n_stages)
    stage_caches = [jax.tree.map(
        lambda x: x[:n_scan].reshape((n_stages, pps) + x.shape[1:]), c)
        for c in caches["scan"]]
    epi_scan_caches = [jax.tree.map(lambda x: x[n_scan:], c)
                       for c in caches["scan"]] if leftover else []

    x_inj = embed_lib.embed_tokens(params["embed"], tokens, cfg, cd)

    def chunk_positions(offs, nv):
        iota = jnp.arange(C)[None]
        pos = jnp.where(iota < nv[:, None], offs[:, None] + iota, -1)
        if cfg.frontend == "vision_patches":
            from repro.models.common import text_positions3
            return pos, text_positions3(pos)
        return pos, pos

    def body(stage_params_l, stage_caches_l, act_l, x_inj, offs_stage,
             valid_stage, tables_stage):
        lp = [jax.tree.map(lambda x: x[0], p) for p in stage_params_l]
        lc = [jax.tree.map(lambda x: x[0], c) for c in stage_caches_l]
        pod = jax.lax.axis_index("pod")
        is_last = pod == n_stages - 1

        x_in = jnp.where(pod == 0, x_inj, act_l[0])
        offs = jax.lax.dynamic_index_in_dim(offs_stage, pod, 0,
                                            keepdims=False)
        nv = jax.lax.dynamic_index_in_dim(valid_stage, pod, 0,
                                          keepdims=False)
        tabs = jax.lax.dynamic_index_in_dim(tables_stage, pod, 0,
                                            keepdims=False)     # (R, P)
        _, p1 = chunk_positions(offs, nv)

        # the chunk's rows are arbitrary slots: run the stage's period
        # slice with the chunk's own page-table rows; pools are shared,
        # the parked per-slot table leaves pass through untouched
        view = [{**c, "page_table": jnp.broadcast_to(
            tabs[None], (pps,) + tabs.shape)} for c in lc]
        y, new_view = model_lib.run_periods(
            lp, x_in, cfg, rt, period_kinds=plan.period_kinds,
            mode="chunk", scan_caches=view, positions=p1)
        new_lc = [{**{k: v.astype(c_old[k].dtype)
                      for k, v in v_new.items() if k.endswith("_pages")},
                   "page_table": c_old["page_table"]}
                  for c_old, v_new in zip(lc, new_view)]

        y_out = jax.lax.psum(
            jnp.where(is_last, y, jnp.zeros_like(y)).astype(jnp.float32),
            "pod").astype(y.dtype)
        y_next = _wire_permute(y, n_stages, wire_dtype)
        new_lc = [jax.tree.map(lambda x: x[None], c) for c in new_lc]
        return y_out, y_next[None], new_lc

    P = jax.sharding.PartitionSpec
    in_specs = (
        [jax.tree.map(lambda _: P("pod"), p) for p in stage_params],
        [jax.tree.map(lambda _: P("pod"), c) for c in stage_caches],
        P("pod"), P(), P(), P(), P(),
    )
    out_specs = (P(), P("pod"),
                 [jax.tree.map(lambda _: P("pod"), c) for c in stage_caches])
    fn = _shard_map(body, mesh=mesh, axis_names={"pod"},
                    in_specs=in_specs, out_specs=out_specs)
    y_out, new_act, new_stage = fn(stage_params, stage_caches, act, x_inj,
                                   offs_stage, valid_stage, tables_stage)

    # epilogue for the draining chunk (replicated; the paper's return link
    # carries (R,) first-token logit rows once per chunk, not activations)
    offs_d = offs_stage[n_stages - 1]
    nv_d = valid_stage[n_stages - 1]
    tabs_d = tables_stage[n_stages - 1]
    pos_d, p1 = chunk_positions(offs_d, nv_d)
    epi_view = {
        "epi_scan": [{**c, "page_table": jnp.broadcast_to(
            tabs_d[None], (c["page_table"].shape[0],) + tabs_d.shape)}
            for c in epi_scan_caches],
        "tail": [{**c, "page_table": tabs_d} for c in caches["tail"]],
    }
    xf, new_epi_scan, new_tail = _epilogue(
        params, epi_scan_params, y_out, cfg, rt, mode="chunk",
        caches=epi_view, positions=p1)
    idx = jnp.clip(lasts, 0, C - 1).reshape(R, 1, 1)
    x_last = jnp.take_along_axis(
        xf, jnp.broadcast_to(idx, (R, 1, xf.shape[-1])), axis=1)[:, 0]
    logits = embed_lib.unembed(params["embed"], x_last, cfg)

    keep = lambda n, o: {**{k: v.astype(o[k].dtype) for k, v in n.items()
                            if k.endswith("_pages")},
                         "page_table": o["page_table"]}
    epi_merged_scan = [keep(n, o) for n, o in
                       zip(new_epi_scan or [], epi_scan_caches)]
    new_tail = [keep(n, o) for n, o in zip(new_tail, caches["tail"])]

    new_scan = []
    for i in range(len(caches["scan"])):
        st = jax.tree.map(lambda x: x.reshape((n_scan,) + x.shape[2:]),
                          new_stage[i])
        if leftover:
            st = jax.tree.map(lambda a, b: jnp.concatenate([a, b], axis=0),
                              st, epi_merged_scan[i])
        new_scan.append(st)
    new_caches = {"scan": new_scan, "tail": new_tail}
    return logits, new_caches, new_act


# ---------------------------------------------------------------------------
# Multi-round circular decode (the §4.3 steady state, compiled)
# ---------------------------------------------------------------------------


def pipeline_decode_rounds(params, tokens, caches, cur_pos,
                           cfg: ModelConfig, rt: Runtime,
                           pcfg: PipelineConfig, *, rounds: int):
    """Greedy-decode ``rounds`` tokens per microbatch in ONE circular pass.

    This is the schedule the paper actually runs in steady state: microbatch
    ``m``'s round-``r`` token is injected at tick ``r·N_B + m``, immediately
    behind its round-``r−1`` drain (legal because N_B ≥ N_S) — fill/drain
    bubbles amortise to (N_S−1)/(R·N_B + N_S − 1).  Sampling (greedy) and
    re-embedding happen replicated across pods on the drained activations;
    the paper's return link carries the (mb,) token ids.

    tokens/cur_pos (n_mb, mb) int32.  Returns (all_tokens (rounds, n_mb,
    mb) int32, new_caches).  Requires N_B ≥ N_S.
    """
    n_s, n_mb, mb = pcfg.n_stages, pcfg.n_microbatches, pcfg.mb_size
    if n_mb < n_s:
        raise ValueError("multi-round circular decode needs N_B >= N_S")
    pps, _ = split_layers(cfg, n_s)
    plan = make_layer_plan(cfg.num_layers, cfg.block_pattern)
    cd = rt.compute_dtype
    n_ticks = rounds * n_mb + n_s - 1

    stage_params, epi_scan_params = split_scan_params(params, cfg, n_s)
    epi_state = {"epi_scan": caches["epi_scan"], "tail": caches["tail"]}

    def body(local_params, local_caches, epi_caches, tokens, cur_pos):
        local_params = [jax.tree.map(lambda x: x[0], p) for p in local_params]
        local_caches = [jax.tree.map(lambda x: x[0], c) for c in local_caches]
        pod = jax.lax.axis_index("pod")
        is_last = pod == n_s - 1

        def embed_mb(tok, pos):
            x = embed_lib.embed_tokens(params["embed"], tok, cfg, cd)
            return x[:, None]                       # (mb, 1, D)

        def _epi_take(epi, start):
            """Per-microbatch view of the (global-batch) epilogue caches."""
            return {
                "epi_scan": [jax.tree.map(
                    lambda l: jax.lax.dynamic_slice_in_dim(l, start, mb, 1),
                    c) for c in epi["epi_scan"]],
                "tail": [jax.tree.map(
                    lambda l: jax.lax.dynamic_slice_in_dim(l, start, mb, 0),
                    c) for c in epi["tail"]],
            }

        def _epi_put(epi, view, start):
            return {
                "epi_scan": [jax.tree.map(
                    lambda f, pth: jax.lax.dynamic_update_slice_in_dim(
                        f, pth.astype(f.dtype), start, 1), c_f, c_v)
                    for c_f, c_v in zip(epi["epi_scan"], view["epi_scan"])],
                "tail": [jax.tree.map(
                    lambda f, pth: jax.lax.dynamic_update_slice_in_dim(
                        f, pth.astype(f.dtype), start, 0), c_f, c_v)
                    for c_f, c_v in zip(epi["tail"], view["tail"])],
            }

        def epilogue_sample(y, pos, epi, out_mb):
            xf = y                                   # (mb, 1, D)
            p1 = pos[:, None]
            if cfg.frontend == "vision_patches":
                from repro.models.common import text_positions3
                p1 = text_positions3(p1)
            start = out_mb * mb
            view = _epi_take(epi, start)
            xf, new_epi, new_tail = _epilogue(
                params, epi_scan_params, xf, cfg, rt, mode="decode",
                caches=view, positions=p1)
            logits = embed_lib.unembed(params["embed"], xf[:, 0], cfg)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return nxt, _epi_put(epi, {"epi_scan": new_epi,
                                       "tail": new_tail}, start)

        def tick(carry, t):
            x, st_caches, epi, toks, pos, outs = carry
            mb_id = (t - pod) % n_mb
            rnd = (t - pod) // n_mb
            active = ((t - pod) >= 0) & (rnd < rounds)
            mb_c = jnp.clip(mb_id, 0, n_mb - 1)

            mb_caches = [jax.tree.map(
                lambda l: jax.lax.dynamic_index_in_dim(l, mb_c, 0,
                                                       keepdims=False), c)
                for c in st_caches]
            pos_mb = jax.lax.dynamic_index_in_dim(pos, mb_c, 0,
                                                  keepdims=False)
            p1 = pos_mb[:, None]
            if cfg.frontend == "vision_patches":
                from repro.models.common import text_positions3
                p1 = text_positions3(p1)
            y, new_mb = model_lib.run_periods(
                local_params, x, cfg, rt, period_kinds=plan.period_kinds,
                mode="decode", scan_caches=mb_caches, positions=p1)
            new_st = []
            for c_all, c_old, c_new in zip(st_caches, mb_caches, new_mb):
                c_new = jax.tree.map(lambda n, o: jnp.where(active, n, o),
                                     c_new, c_old)
                new_st.append(jax.tree.map(
                    lambda l, n: jax.lax.dynamic_update_index_in_dim(
                        l, n.astype(l.dtype), mb_c, 0), c_all, c_new))

            # drain: the last pod finishes microbatch (t-(n_s-1)) % n_mb;
            # broadcast its activation, run the epilogue + greedy sampling
            # replicated, append the token behind the pipe for next round
            out_id = t - (n_s - 1)
            out_mb = jnp.clip(out_id % n_mb, 0, n_mb - 1)
            out_rnd = out_id // n_mb
            out_valid = (out_id >= 0) & (out_rnd < rounds)
            y_b = jax.lax.psum(
                jnp.where(is_last, y, jnp.zeros_like(y)).astype(jnp.float32),
                "pod").astype(y.dtype)
            pos_out = jax.lax.dynamic_index_in_dim(pos, out_mb, 0,
                                                   keepdims=False)
            nxt, new_epi = epilogue_sample(y_b, pos_out, epi, out_mb)
            epi = jax.tree.map(lambda n, o: jnp.where(out_valid, n, o),
                               new_epi, epi)
            toks = jnp.where(out_valid,
                             toks.at[out_mb].set(nxt), toks)
            pos = jnp.where(out_valid, pos.at[out_mb].add(1), pos)
            outs = jnp.where(
                out_valid,
                jax.lax.dynamic_update_index_in_dim(
                    outs, jax.lax.dynamic_update_index_in_dim(
                        jax.lax.dynamic_index_in_dim(
                            outs, jnp.clip(out_rnd, 0, rounds - 1), 0,
                            keepdims=False),
                        nxt, out_mb, 0),
                    jnp.clip(out_rnd, 0, rounds - 1), 0),
                outs)

            # ship downstream; pod 0 injects the next tick's token
            y_next = jax.lax.ppermute(
                y, "pod", [(i, (i + 1) % n_s) for i in range(n_s)])
            nxt_mb = jnp.clip((t + 1) % n_mb, 0, n_mb - 1)
            inj_tok = jax.lax.dynamic_index_in_dim(toks, nxt_mb, 0,
                                                   keepdims=False)
            inj_pos = jax.lax.dynamic_index_in_dim(pos, nxt_mb, 0,
                                                   keepdims=False)
            inj = embed_mb(inj_tok, inj_pos)
            x_next = jnp.where(pod == 0, inj, y_next)
            return (x_next, new_st, epi, toks, pos, outs), None

        x0 = embed_mb(tokens[0], cur_pos[0]) * jnp.where(
            pod == 0, 1.0, 0.0).astype(cd)
        outs0 = jnp.zeros((rounds, n_mb, mb), jnp.int32)
        (x, st, epi, toks, pos, outs), _ = jax.lax.scan(
            tick, (x0, local_caches, epi_caches, tokens, cur_pos, outs0),
            jnp.arange(n_ticks))
        st = [jax.tree.map(lambda x: x[None], c) for c in st]
        return outs, st, epi

    P = jax.sharding.PartitionSpec
    in_specs = (
        [jax.tree.map(lambda _: P("pod"), p) for p in stage_params],
        [jax.tree.map(lambda _: P("pod"), c) for c in caches["stage"]],
        jax.tree.map(lambda _: P(), epi_state),
        P(), P(),
    )
    out_specs = (P(),
                 [jax.tree.map(lambda _: P("pod"), c)
                  for c in caches["stage"]],
                 jax.tree.map(lambda _: P(), epi_state))
    fn = _shard_map(body, mesh=_ambient_mesh(), axis_names={"pod"},
                    in_specs=in_specs, out_specs=out_specs)
    outs, new_stage, new_epi = fn(stage_params, caches["stage"], epi_state,
                                  tokens, cur_pos)
    new_caches = {"stage": new_stage, "epi_scan": new_epi["epi_scan"],
                  "tail": new_epi["tail"]}
    return outs, new_caches
