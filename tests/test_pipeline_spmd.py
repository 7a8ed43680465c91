"""SPMD pipeline equivalence, run in a fresh interpreter with 8 fake devices
(so the rest of the suite keeps the real single-device backend).

The subprocess asserts, per arch family: pipelined prefill+decode over a
(pod=2, data=2, model=2) mesh == the plain single-program path.
"""

import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from repro.config import get_arch, reduced_config
from repro.models import model as M
from repro.models.common import Runtime
from repro.core import pipeline as PL

rt = Runtime(param_dtype=jnp.float32, compute_dtype=jnp.float32)
mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 3)
arch = os.environ["PIPE_ARCH"]
cfg0 = get_arch(arch)
period = len(cfg0.block_pattern)
cfg = reduced_config(cfg0, num_layers=2 * period + (2 if period > 1 else 1))
params = M.init_params(cfg, jax.random.PRNGKey(0), rt)
n_mb, mb, S, cap = 3, 4, 10, 32
B = n_mb * mb
pcfg = PL.PipelineConfig(n_stages=2, n_microbatches=n_mb, mb_size=mb)
toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab_size)
logits_ref, caches_ref = M.prefill(params, {"tokens": toks}, cfg, rt, cap)
tok = jnp.argmax(logits_ref, -1).astype(jnp.int32)
cur = jnp.full((B,), S, jnp.int32)
dec_ref, _ = M.decode_step(params, tok, caches_ref, cur, cfg, rt)
with mesh:
    pcaches = PL.init_pipeline_caches(cfg, pcfg, cap, rt)
    pl_logits, pcaches = jax.jit(
        lambda p, t, c: PL.pipeline_prefill(p, t, c, cfg, rt, pcfg))(
        params, toks.reshape(n_mb, mb, S), pcaches)
    err_pf = float(jnp.max(jnp.abs(pl_logits.reshape(B, -1) - logits_ref)))
    tok2 = jnp.argmax(pl_logits.reshape(B, -1), -1).astype(jnp.int32)
    dec_pl, pcaches = jax.jit(
        lambda p, t, c, cp: PL.pipeline_decode_step(p, t, c, cp, cfg, rt,
                                                    pcfg))(
        params, tok2.reshape(n_mb, mb), pcaches, cur.reshape(n_mb, mb))
    err_dec = float(jnp.max(jnp.abs(dec_pl.reshape(B, -1) - dec_ref)))
print(f"errs {err_pf:.3e} {err_dec:.3e}")
assert err_pf < 2e-3 and err_dec < 2e-3, (err_pf, err_dec)
print("OK")
"""


@pytest.mark.slow
@pytest.mark.parametrize("arch", ["yi-9b", "gemma3-12b", "xlstm-1.3b",
                                  "recurrentgemma-9b"])
def test_pipeline_equals_plain(arch):
    env = dict(os.environ)
    env["PIPE_ARCH"] = arch
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                       capture_output=True, text=True, timeout=560)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-2000:]}"
    assert "OK" in r.stdout


@pytest.mark.slow
def test_dryrun_one_cell_compiles():
    """End-to-end 256-device lower+compile of one real cell (the smallest)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "gemma3-1b",
         "--shape", "decode_32k", "--mesh", "single"],
        env=env, capture_output=True, text=True, timeout=560)
    assert r.returncode == 0, r.stdout + r.stderr[-2000:]
    assert '"ok": true' in r.stdout


ROUNDS_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.config import get_arch, reduced_config
from repro.models import model as M
from repro.models.common import Runtime
from repro.core import pipeline as PL

rt = Runtime(param_dtype=jnp.float32, compute_dtype=jnp.float32)
mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 3)
cfg0 = get_arch(os.environ["PIPE_ARCH"])
period = len(cfg0.block_pattern)
cfg = reduced_config(cfg0, num_layers=2 * period + (2 if period > 1 else 1))
params = M.init_params(cfg, jax.random.PRNGKey(0), rt)
n_mb, mb, S, cap, R = 3, 4, 6, 48, 4
B = n_mb * mb
pcfg = PL.PipelineConfig(n_stages=2, n_microbatches=n_mb, mb_size=mb)
toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab_size)
logits, caches = M.prefill(params, {"tokens": toks}, cfg, rt, cap)
tok = jnp.argmax(logits, -1).astype(jnp.int32)
ref = []
for r in range(R):
    ref.append(np.asarray(tok))
    logits, caches = M.decode_step(params, tok, caches,
                                   jnp.full((B,), S + r, jnp.int32), cfg, rt)
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
ref = np.stack(ref)
with mesh:
    pcaches = PL.init_pipeline_caches(cfg, pcfg, cap, rt)
    pl_logits, pcaches = jax.jit(
        lambda p, t, c: PL.pipeline_prefill(p, t, c, cfg, rt, pcfg))(
        params, toks.reshape(n_mb, mb, S), pcaches)
    tok0 = jnp.argmax(pl_logits.reshape(B, -1), -1).astype(jnp.int32)
    outs, _ = jax.jit(lambda p, t, c, cp: PL.pipeline_decode_rounds(
        p, t, c, cp, cfg, rt, pcfg, rounds=R))(
        params, tok0.reshape(n_mb, mb), pcaches,
        jnp.full((n_mb, mb), S, jnp.int32))
got = np.asarray(outs).reshape(R, B)
assert (got[:R - 1] == ref[1:]).all(), (got[:2], ref[1:3])
print("OK")
"""


@pytest.mark.slow
@pytest.mark.parametrize("arch", ["yi-9b", "gemma3-12b"])
def test_multiround_circular_decode(arch):
    """R tokens in one circular pass == R sequential pipelined decodes —
    the paper's steady-state schedule, with sampling on the return link."""
    env = dict(os.environ)
    env["PIPE_ARCH"] = arch
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, "-c", ROUNDS_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=560)
    assert r.returncode == 0, f"{r.stdout}\n{r.stderr[-2000:]}"
