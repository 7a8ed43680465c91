"""A run whose timed path is broken underneath comes out not correct.

Each fault is planted in the decode tick of the local backend, the
program the window drives, and the rest of a run goes as usual on a tiny
cell on the CPU.  The faults a one-chip serving cell can have: a step
that returns its state unchanged, half of the batch left out (its rows
answered from the other half), and a token altered where it is produced.
(The exchange between chips is planted in the four-stage tick of
``test_bench_pipeline.py``.)  Each
fault is judged under the tiny cell's limits and under the limits of each
cell that ``BENCHMARK.json`` commits.
"""

import pytest

import bench_cpu
from bench import check, peaks, run, spec


def _state_unchanged(orig):
    def fn(params, caches, *a, **kw):
        toks, lps, _ = orig(params, caches, *a, **kw)
        return toks, lps, caches
    return fn


def _half_batch(orig):
    def fn(*a, **kw):
        toks, lps, caches = orig(*a, **kw)
        h = toks.shape[0] // 2
        return (toks.at[h:].set(toks[:h]), lps.at[h:].set(lps[:h]),
                caches)
    return fn


def _token_altered(orig):
    def fn(*a, cfg, **kw):
        toks, lps, caches = orig(*a, cfg=cfg, **kw)
        return (toks + 1) % cfg.vocab_size, lps, caches
    return fn


FAULTS = {"state_unchanged": _state_unchanged, "half_batch": _half_batch,
          "token_altered": _token_altered}
LIMITS = {"tiny": None, **bench_cpu.committed_limits()}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_cpu.make_root(tmp_path_factory.mktemp("faults"))


def _run(root):
    cell = spec.resolve("tiny.decode-offload", root)
    return run.run(cell, 2 ** 32 + 1, 1.5, False, require_tpu=False,
                   peaks=peaks.PEAKS["TPU v5 lite"])


@pytest.fixture(scope="module")
def faulted(root):
    """One run of the tiny cell with each fault planted, by fault name."""
    from repro.serving.backend import LocalBackend
    orig = LocalBackend.__dict__["_decode_fn"]
    out = {}
    try:
        for name, fault in FAULTS.items():
            LocalBackend._decode_fn = staticmethod(fault(orig.__func__))
            out[name] = _run(root)
    finally:
        LocalBackend._decode_fn = orig
    return out


def test_sound_run_is_correct(root):
    res = _run(root)
    assert res["correct"]
    for name, limits in LIMITS.items():
        if limits is not None:
            assert check.verdict(res["readings"], limits)["ok"], name


@pytest.mark.parametrize("limits", list(LIMITS))
@pytest.mark.parametrize("fault", list(FAULTS))
def test_fault_is_caught(faulted, fault, limits):
    """Under the tiny cell's own limits the whole run comes out not
    correct; under each committed cell's limits its numbers fail them."""
    res = faulted[fault]
    if LIMITS[limits] is None:
        assert not res["correct"], res["check"]
    else:
        assert not check.verdict(res["readings"], LIMITS[limits])["ok"], \
            (limits, res["readings"])
