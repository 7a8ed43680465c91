"""repro-audit: static lint passes (against fixture files), suppression
mechanics, the clean-tree gate, the runtime invariant auditor, and the
no-retrace-after-warmup regression on both backends."""

import os
import re
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from conftest import tiny
from repro.analysis.invariants import InvariantViolation, jit_cache_size
from repro.analysis.lint import AuditConfig, run_lint
from repro.models import model as M
from repro.serving.engine import OfflineEngine
from repro.serving.kv_cache import PoolConfig
from repro.serving.request import Request, SamplingParams, Status

REPO = Path(__file__).resolve().parents[1]
FIXTURES = Path(__file__).parent / "fixtures" / "analysis"


def _marks(path: Path):
    """{rule: [lineno, ...]} from ``# LINT-EXPECT: <rule>`` markers."""
    out = {}
    for i, line in enumerate(path.read_text().splitlines(), start=1):
        m = re.search(r"LINT-EXPECT:\s*([\w-]+)", line)
        if m:
            out.setdefault(m.group(1), []).append(i)
    return out


# ------------------------------------------------------------- lint ---


def test_fixture_host_sync_fires_once():
    path = FIXTURES / "fixture_host_sync.py"
    cfg = AuditConfig(hot_roots=["fixture_host_sync:tick_loop"],
                      traced_fns=[])
    vs = run_lint([path], config=cfg)
    assert [(v.rule, v.line) for v in vs] == \
        [("host-sync", _marks(path)["host-sync"][0])]
    assert vs[0].path == str(path)
    assert "device_get" in vs[0].msg


def test_fixture_prng_rules_fire_once_each():
    path = FIXTURES / "fixture_prng.py"
    marks = _marks(path)
    vs = run_lint([path], config=AuditConfig(hot_roots=[], traced_fns=[]))
    got = sorted((v.rule, v.line) for v in vs)
    assert got == sorted([
        ("prng-fold-drop", marks["prng-fold-drop"][0]),
        ("prng-reuse", marks["prng-reuse"][0]),
    ])
    drop = next(v for v in vs if v.rule == "prng-fold-drop")
    assert "token_idx" in drop.msg   # says WHAT the short chain dropped


def test_fixture_retrace_rules_fire_once_each():
    path = FIXTURES / "fixture_retrace.py"
    marks = _marks(path)
    cfg = AuditConfig(hot_roots=["fixture_retrace:hot_step"],
                      traced_fns=["fixture_retrace:tick_fn"])
    vs = run_lint([path], config=cfg)
    got = sorted((v.rule, v.line) for v in vs)
    assert got == sorted([
        ("retrace-jit", marks["retrace-jit"][0]),
        ("retrace-nonhashable", marks["retrace-nonhashable"][0]),
        ("retrace-branch", marks["retrace-branch"][0]),
    ])


def test_fixture_offload_sync_fires_once():
    path = FIXTURES / "fixture_offload_sync.py"
    cfg = AuditConfig(
        hot_roots=[], traced_fns=[],
        offload_windows=["fixture_offload_sync:Offloader.ensure_resident"])
    vs = run_lint([path], config=cfg)
    assert [(v.rule, v.line) for v in vs] == \
        [("offload-sync", _marks(path)["offload-sync"][0])]
    # the message tells the reader WHAT to do, not just what fired
    assert "enqueued" in vs[0].msg


def test_fixture_obs_hot_path_fires_twice():
    path = FIXTURES / "fixture_obs.py"
    marks = _marks(path)
    cfg = AuditConfig(hot_roots=["fixture_obs:hot_step"],
                      traced_fns=["fixture_obs:tick_fn"])
    vs = run_lint([path], config=cfg)
    got = sorted((v.rule, v.line) for v in vs)
    assert got == sorted(
        ("obs-hot-path", ln) for ln in marks["obs-hot-path"])
    in_jit = next(v for v in vs if "tick-jit" in v.msg)
    assert "host-side" in in_jit.msg     # says WHY the recorder can't run
    dev = next(v for v in vs if "materialises" in v.msg)
    assert "host scalars" in dev.msg     # ... and what to record instead


@pytest.mark.parametrize("case", ["tick-jit", "materialises"])
def test_fixture_obs_span_is_a_recording_call(case):
    """``obs.span(...)`` is a recording call: opened inside a tick-jit
    body, or fed a device value in the hot path, it is flagged; a span
    given only a name and the recorder is not."""
    path = FIXTURES / "fixture_obs_span.py"
    marks = _marks(path)
    cfg = AuditConfig(hot_roots=["fixture_obs_span:hot_step"],
                      traced_fns=["fixture_obs_span:tick_fn"])
    vs = run_lint([path], config=cfg)
    assert sorted((v.rule, v.line) for v in vs) == sorted(
        ("obs-hot-path", ln) for ln in marks["obs-hot-path"])
    hit = [v for v in vs if case in v.msg]
    assert len(hit) == 1 and "obs.span" in hit[0].msg
    in_jit_line, device_line = marks["obs-hot-path"]
    assert hit[0].line == (in_jit_line if case == "tick-jit"
                           else device_line)


def test_suppression_with_reason_silences(tmp_path):
    f = tmp_path / "mod_sync.py"
    f.write_text(
        "import jax\n\n\n"
        "def tick_loop(x):\n"
        "    # repro-audit: allow(host-sync) — return link needs host "
        "tokens\n"
        "    return jax.device_get(x)\n")
    cfg = AuditConfig(hot_roots=["mod_sync:tick_loop"], traced_fns=[])
    assert run_lint([f], config=cfg) == []
    # a reasoned, used suppression also survives the strict gate
    assert run_lint([f], config=cfg, strict_suppressions=True) == []


def test_strict_suppressions_flag_unreasoned_stale_and_unknown(tmp_path):
    f = tmp_path / "mod_stale.py"
    f.write_text(
        "def helper():\n"
        "    # repro-audit: allow(host-sync)\n"        # no reason
        "    return 1\n\n\n"
        "def other():\n"
        "    # repro-audit: allow(no-such-rule) — covering nothing\n"
        "    return 2\n\n\n"
        "def third():\n"
        "    # repro-audit: allow(prng-reuse) — stale after a fix\n"
        "    return 3\n")
    cfg = AuditConfig(hot_roots=[], traced_fns=[])
    # default mode tolerates them all
    assert run_lint([f], config=cfg) == []
    vs = run_lint([f], config=cfg, strict_suppressions=True)
    assert sorted(v.rule for v in vs) == \
        ["bad-suppression", "bad-suppression", "unused-suppression"]


def test_docstring_mention_is_not_a_suppression(tmp_path):
    # the allow() syntax quoted in a docstring must not silence anything
    f = tmp_path / "mod_doc.py"
    f.write_text(
        'import jax\n\n\n'
        'def tick_loop(x):\n'
        '    """# repro-audit: allow(host-sync) — quoted, not real"""\n'
        '    return jax.device_get(x)\n')
    cfg = AuditConfig(hot_roots=["mod_doc:tick_loop"], traced_fns=[])
    vs = run_lint([f], config=cfg)
    assert [v.rule for v in vs] == ["host-sync"]


def test_clean_tree_lint_exits_zero():
    """The committed src/ tree passes its own audit, strict suppressions
    included — this is the same command the CI audit job runs."""
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    r = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "src",
         "--strict-suppressions"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, f"{r.stdout}\n{r.stderr[-2000:]}"
    assert "repro-audit: clean" in r.stdout


# --------------------------------------------------- runtime auditor ---


def _small_engine(rt, strict=True, mb=1, n_mb=1, max_new=4):
    cfg = tiny("yi-9b")
    params = M.init_params(cfg, jax.random.PRNGKey(0), rt)
    pool = PoolConfig(page_size=8, n_local_pages=16, max_pages_per_seq=4)
    sp = SamplingParams(temperature=0.0, max_new_tokens=max_new)
    eng = OfflineEngine(cfg, params, rt, mb_size=mb, num_microbatches=n_mb,
                        pool=pool, sampling=sp, strict=strict)
    return eng, cfg, sp


def test_strict_default_follows_env(monkeypatch, rt):
    monkeypatch.setenv("REPRO_STRICT", "0")
    eng, _, _ = _small_engine(rt, strict=None)
    assert eng.auditor is None
    monkeypatch.setenv("REPRO_STRICT", "1")
    eng, _, _ = _small_engine(rt, strict=None)
    assert eng.auditor is not None
    # explicit flag beats the environment
    eng, _, _ = _small_engine(rt, strict=False)
    assert eng.auditor is None


def test_auditor_catches_page_leak(rt):
    eng, cfg, sp = _small_engine(rt)
    eng.submit([Request(0, [3, 4, 5], sp)])
    eng.step()
    eng.auditor.after_step()          # consistent so far
    # leak one free page out of the allocator's books
    page = next(iter(eng.alloc._free_local))
    eng.alloc._free_local.remove(page)
    with pytest.raises(InvariantViolation, match="page"):
        eng.auditor.after_step()


def test_auditor_catches_fsm_backstep(rt):
    eng, cfg, sp = _small_engine(rt)
    eng.submit([Request(0, [3, 4, 5], sp)])
    done = eng.run(max_steps=100)
    assert len(done) == 1
    eng.finished[0].status = Status.DECODING   # illegal rewind
    with pytest.raises(InvariantViolation, match="fsm"):
        eng.auditor.after_step()


def test_auditor_catches_offload_breaches(rt):
    from repro.core.offload import DoubleBufferOffloader
    cfg = tiny("yi-9b")
    params = M.init_params(cfg, jax.random.PRNGKey(0), rt)
    pool = PoolConfig(page_size=8, n_local_pages=16, n_global_pages=4,
                      max_pages_per_seq=4)
    sp = SamplingParams(temperature=0.0, max_new_tokens=4)
    off = DoubleBufferOffloader(pool, 2)
    eng = OfflineEngine(cfg, params, rt, mb_size=1, num_microbatches=2,
                        pool=pool, sampling=sp, offloader=off, strict=True)
    eng.auditor.after_step()                   # consistent so far
    # (a) parity breach: pool 0 must only ever host even microbatches
    off.resident[0] = 1
    with pytest.raises(InvariantViolation, match="parity"):
        eng.auditor.after_step()
    off.resident[0] = None
    # (b) stale host copy kept for a resident microbatch
    off.resident[1] = 1
    off._host[1] = []
    with pytest.raises(InvariantViolation, match="host-store"):
        eng.auditor.after_step()
    del off._host[1]
    # (c) counters must be monotone for the offloader's lifetime
    off.swap_count = 5
    eng.auditor.after_step()
    off.swap_count = 2
    with pytest.raises(InvariantViolation, match="backward"):
        eng.auditor.after_step()


def test_jit_cache_size_probe():
    f = jax.jit(lambda x: x + 1)
    assert jit_cache_size(f) == 0
    f(jax.numpy.ones((3,)))
    assert jit_cache_size(f) == 1
    f(jax.numpy.ones((4,)))           # new shape → second trace
    assert jit_cache_size(f) == 2
    assert jit_cache_size(lambda x: x) is None   # not a jit: cannot check


# ------------------------------------------- retrace regression gate ---


def test_local_backend_no_retrace_after_warmup(rt):
    """Mixed prefill+decode with slot churn: after the run, every serve
    jit the backend exposes holds exactly one compiled trace."""
    eng, cfg, sp = _small_engine(rt, mb=2, n_mb=2, max_new=5)
    rng = np.random.RandomState(11)
    reqs = [Request(i, list(rng.randint(1, cfg.vocab_size,
                                        rng.randint(3, 12))), sp)
            for i in range(7)]        # 7 > 4 slots → replenishment
    eng.submit(reqs)
    done = eng.run(max_steps=400)
    assert len(done) == 7
    sizes = {name: jit_cache_size(fn)
             for name, fn in eng.backend.jit_entries().items()}
    assert sizes, "backend exposes no jit entries"
    bad = {k: v for k, v in sizes.items() if v is not None and v > 1}
    assert not bad, f"retraced mid-serve: {bad} (all: {sizes})"
    assert any(v == 1 for v in sizes.values()), \
        f"nothing compiled — probe is dead: {sizes}"


PIPE_RETRACE_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax, jax.numpy as jnp
import numpy as np
from repro.analysis.invariants import jit_cache_size
from repro.config import get_arch, reduced_config
from repro.core.offload import DoubleBufferOffloader
from repro.models import model as M
from repro.models.common import Runtime
from repro.serving.engine import OfflineEngine
from repro.serving.kv_cache import PoolConfig
from repro.serving.request import Request, SamplingParams

rt = Runtime(param_dtype=jnp.float32, compute_dtype=jnp.float32)
cfg0 = get_arch("yi-9b")
period = len(cfg0.block_pattern)
cfg = reduced_config(cfg0, num_layers=2 * period + (2 if period > 1 else 1))
params = M.init_params(cfg, jax.random.PRNGKey(0), rt)
pool = PoolConfig(page_size=4, n_local_pages=32, n_global_pages=12,
                  max_pages_per_seq=6)
sp = SamplingParams(temperature=0.0, max_new_tokens=5)
eng = OfflineEngine(cfg, params, rt, mb_size=2, num_microbatches=3,
                    pool=pool, sampling=sp, backend="pipelined",
                    n_stages=2, offloader=DoubleBufferOffloader(pool, 3),
                    strict=True)
rng = np.random.RandomState(11)
reqs = [Request(i, list(rng.randint(1, cfg.vocab_size,
                                    rng.randint(3, 10))), sp)
        for i in range(8)]            # 8 > 6 slots: prefill amid decode
eng.submit(reqs)
done = eng.run(max_steps=600)
assert len(done) == 8, len(done)
sizes = {k: jit_cache_size(f) for k, f in eng.backend.jit_entries().items()}
bad = {k: v for k, v in sizes.items() if v is not None and v > 1}
assert not bad, f"retraced mid-serve: {sizes}"
assert any(v == 1 for v in sizes.values()), sizes
print("OK", sizes)
"""


@pytest.mark.slow
def test_pipelined_backend_no_retrace_after_warmup():
    """Same gate on the 2-stage pipelined backend (fresh interpreter with
    2 fake CPU devices): mixed prefill+decode with offloading, then every
    tick jit — `_tick_jit`, `_pf_tick_jit`, the per-length prefill jits —
    must hold exactly one compiled trace."""
    from equivalence import subprocess_env
    r = subprocess.run([sys.executable, "-c", PIPE_RETRACE_SCRIPT],
                       env=subprocess_env(), capture_output=True,
                       text=True, timeout=560)
    assert r.returncode == 0, f"{r.stdout}\n{r.stderr[-2000:]}"
    assert "OK" in r.stdout
