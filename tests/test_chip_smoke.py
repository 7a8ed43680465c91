"""``chip_smoke.py`` rehearsed on the CPU at a tiny size, its refusals,
and the compile-cache helper it calls first.

The script's phases are plain functions over a config and a ``Sizes``;
these tests call them with a reduced config (interpret-mode kernels, fp32)
so a wrong path, argument or control flow fails here and not on the chip.
The four-stage phase runs on four virtual CPU devices in a fresh
interpreter."""

import importlib.util
import json
import os
import shutil
import subprocess
import sys
import textwrap

from equivalence import subprocess_env

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")

TINY = dict(n_requests=4, prompt_lo=6, prompt_hi=20, max_new=4,
            page_size=4, n_local_pages=12, n_global_pages=8,
            prefill_chunk=8, mb_size=1, num_microbatches=4,
            online_requests=3, system_pages=2, online_new=3,
            online_suffix=6, full_requests=2, full_new=3)


def _load_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod      # dataclasses resolve their module
    spec.loader.exec_module(mod)
    return mod


def test_one_chip_phases_run_at_tiny_size(capsys):
    from repro.config import get_arch, reduced_config
    smoke = _load_smoke()
    cfg = reduced_config(get_arch("yi-9b"), num_layers=2)
    smoke.run_one_chip(cfg, smoke.Sizes(**TINY), 0, smoke.CompileLog())
    out = capsys.readouterr().out
    for phase in ("1 local", "2 pipelined, one stage",
                  "3 online, prefix cache"):
        assert f"phase {phase}: ok" in out
    assert "online: prefix hits 2, hit tokens 16" in out
    # the swap really moved pages between the pool and the host store
    assert "offload swaps 0" not in out and " 0 bytes moved" not in out


def test_greedy_divergence_is_judged_by_the_reference_logits(capsys):
    """The reference logits pick the served greedy tokens, and a flip to
    a token far below the top fails the comparison instead of passing as
    a tie."""
    import dataclasses

    import numpy as np

    from repro.config import get_arch, reduced_config
    from repro.serving.llm import LLM
    from repro.serving.request import SamplingParams
    smoke = _load_smoke()
    cfg = reduced_config(get_arch("yi-9b"), num_layers=2)
    sizes = smoke.Sizes(**TINY)
    llm = LLM(cfg, config=smoke.engine_config(sizes, "local", 0,
                                              offload=False))
    prompts = smoke.make_prompts(np.random.RandomState(1), 2, 6, 20,
                                 cfg.vocab_size)
    sp = SamplingParams(temperature=0.0, max_new_tokens=4)
    outs = llm.generate(prompts, sp)
    probe = smoke.LogitProbe(llm.params, cfg, llm.rt)
    for o in outs:
        for i, tok in enumerate(o.token_ids):
            lg = probe.logits(o.prompt + o.token_ids[:i])
            assert lg.shape == (cfg.vocab_size,) and int(np.argmax(lg)) == tok
    assert smoke.compare_greedy(outs, outs, [sp] * 2, probe, "same") == 0

    o = outs[0]
    worst = int(np.argmin(probe.logits(o.prompt + o.token_ids[:1])))
    flipped = [dataclasses.replace(o, token_ids=[o.token_ids[0], worst]
                                   + o.token_ids[2:])] + outs[1:]
    try:
        smoke.compare_greedy(outs, flipped, [sp] * 2, probe, "flip")
    except smoke.SmokeFailure as e:
        assert "diverges at position 1" in str(e)
    else:
        raise AssertionError("a flip to the lowest logit passed as a tie")
    assert "first differs at generated position 1" in capsys.readouterr().out


_FOUR = textwrap.dedent("""
    import importlib.util, os, sys
    from repro.config import get_arch, reduced_config
    spec = importlib.util.spec_from_file_location("chip_smoke", sys.argv[1])
    smoke = sys.modules["chip_smoke"] = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    yi = get_arch("yi-9b")
    smoke.run_four_chips(reduced_config(yi, num_layers=4),
                         reduced_config(yi, num_layers=8),
                         smoke.Sizes(**eval(sys.argv[2])), 0,
                         smoke.CompileLog())
    print("FOUR-OK")
""")


def test_four_stage_phase_runs_on_virtual_devices():
    env = subprocess_env({
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
        "JAX_PLATFORMS": "cpu"})
    r = subprocess.run([sys.executable, "-c", _FOUR, SCRIPT, repr(TINY)],
                       env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "FOUR-OK" in r.stdout
    for phase in ("b full depth (8 layers), 4 stages",
                  "a 4 layers, local on device 0", "a 4 layers, 4 stages"):
        assert f"phase {phase}: ok" in r.stdout


def _run_script(cwd, env_extra):
    env = subprocess_env(env_extra)
    return subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def _no_result_line(stdout: str) -> bool:
    lines = stdout.strip().splitlines()
    if not lines:
        return True
    try:
        json.loads(lines[-1])
    except ValueError:
        return True
    return False


def test_refuses_a_cpu_and_names_it():
    r = _run_script(ROOT, {"JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert "'cpu'" in r.stderr
    assert _no_result_line(r.stdout)


def test_fails_without_the_repo_beside_it(tmp_path):
    shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    env = {"JAX_PLATFORMS": "cpu", "PYTHONPATH": ""}
    r = _run_script(str(tmp_path), env)
    assert r.returncode != 0
    assert "repro package" in r.stderr
    assert _no_result_line(r.stdout)


_CACHE = textwrap.dedent("""
    import os, sys, jax, jax.numpy as jnp
    from repro.launch.cache import REPO_CACHE_DIR, enable_compile_cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    path = enable_compile_cache()
    assert jax.config.jax_compilation_cache_dir == path, path
    if sys.argv[1] == "unset":
        assert path == REPO_CACHE_DIR, path
    else:
        assert path == sys.argv[1], path
        jax.jit(lambda x: x * 3 + 1)(jnp.arange(7.0)).block_until_ready()
    print("CACHE-OK")
""")


def test_compile_cache_follows_the_environment(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, entries land there and the
    helper sets nothing; unset, the helper names <repo>/.jax_cache."""
    set_dir = str(tmp_path / "cache")
    r = subprocess.run([sys.executable, "-c", _CACHE, set_dir],
                       env=subprocess_env({"JAX_PLATFORMS": "cpu",
                                           "JAX_COMPILATION_CACHE_DIR":
                                               set_dir}),
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0 and "CACHE-OK" in r.stdout, r.stderr[-2000:]
    assert os.listdir(set_dir)

    env = subprocess_env({"JAX_PLATFORMS": "cpu"})
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    r = subprocess.run([sys.executable, "-c", _CACHE, "unset"], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0 and "CACHE-OK" in r.stdout, r.stderr[-2000:]
    from repro.launch.cache import REPO_CACHE_DIR
    assert REPO_CACHE_DIR == os.path.join(ROOT, ".jax_cache")
