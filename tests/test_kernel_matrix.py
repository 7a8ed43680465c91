"""Exhaustive attention-kernel matrix vs the pure-jnp oracles.

Sweeps ``page_size x Dh x G x window x dtype x seq-len-edge`` (edges: 1,
page_size-1, page_size, max) for the paged decode kernel and the
analogous block-relative edges for the flash kernel, all in interpret
mode on CPU.  The full cross product runs under ``-m slow``; tier-1 runs
a seeded subsample so every axis stays exercised per-commit without the
interpret-mode bill.

Ragged cases mix rows of length 0, 1, page_size, page_size + 1 and the
table's whole extent in one batch, at GQA groups 1-16, 1-8 KV heads and
head dims 64-256, with live page counts that ``pages_per_block`` does not
divide and windows whose first visible token falls inside a page.

Also pins properties the sweeps alone can't see:

* ``pages_per_block`` is a pure schedule knob — every ppb choice must
  match the oracle on the same inputs;
* only a row's live pages are read: NaN-poisoning every pool page outside
  the rows' live page ranges, or pointing the table slots past each row's
  live pages at other rows' pages, must leave the output *bitwise*
  unchanged.
"""

import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.paged_attention import (paged_decode_attention,
                                           tuned_pages_per_block)

# ---------------------------------------------------------------- axes --

PAGED_AXES = list(itertools.product(
    (8, 16, 32),                    # page_size
    (64, 128),                      # Dh
    (1, 2, 4),                      # G = h // hk
    (0, 1),                         # window: off / ~1.5 pages (resolved below)
    (jnp.float32, jnp.bfloat16),
    ("one", "page-1", "page", "max"),
))

FLASH_AXES = list(itertools.product(
    (64, 128, 256),                 # Dh
    (1, 2, 4),                      # G
    (0, 17),                        # window
    (jnp.float32, jnp.bfloat16),
    (1, 31, 32, 96),                # seq edges around the 32-wide blocks
))


# (page_size, Dh, G, window, dtype, "ragged", Hk, pages_per_block)
RAGGED_CASES = [
    (16, 128, 8, 0, jnp.bfloat16, "ragged", 4, 0),     # yi-9b's shape
    (16, 128, 7, 1, jnp.bfloat16, "ragged", 8, 2),     # yi-34b's shape
    (8, 64, 2, 0, jnp.float32, "ragged", 1, 2),
    (8, 256, 16, 1, jnp.float32, "ragged", 1, 4),
    (8, 128, 1, 0, jnp.float32, "ragged", 8, 3),
    (16, 128, 2, 1, jnp.float32, "ragged", 4, 4),
    (8, 256, 7, 0, jnp.bfloat16, "ragged", 4, 1),
    (16, 64, 16, 1, jnp.bfloat16, "ragged", 1, 3),
]


def _subsample(axes, n, seed):
    rng = np.random.RandomState(seed)
    idx = rng.choice(len(axes), size=min(n, len(axes)), replace=False)
    return [axes[i] for i in sorted(idx)]


_CASES_RUN = itertools.count(1)


@pytest.fixture(autouse=True)
def _bound_compiled_maps():
    """Every sweep case compiles distinct-shape jits; across the full
    matrix the mmapped executables alone would eat a large bite of
    ``vm.max_map_count`` (the suite-wide budget — see conftest).  Drop
    them every few dozen cases; each case compiles its own shapes, so
    cross-case cache hits are rare anyway."""
    yield
    if next(_CASES_RUN) % 32 == 0:
        jax.clear_caches()


def _tol(dtype):
    return 3e-5 if dtype == jnp.float32 else 3e-2


# ---------------------------------------------------------------- paged --


def _lens(edge, page, smax, win_sel):
    if edge == "ragged":
        # empty rows first, inside and last; five pages at the most
        if win_sel == 0:
            return [0, page + 1, smax, 1, 0, page]
        return [page + 1, 0, smax, 1, page, 0]
    by_edge = {"one": 1, "page-1": page - 1, "page": page, "max": smax}
    # row 0 sits at the edge; row 1 at an unrelated interior length
    return [by_edge[edge], min(smax, page + 3)]


def _run_paged(case, pages_per_block=0):
    page, dh, g, win_sel, dtype, edge, *rest = case
    hk, pages_per_block = rest if rest else (2, pages_per_block)
    maxp = 5 if edge == "ragged" else 4
    h = hk * g
    smax = page * maxp
    window = 0 if win_sel == 0 else page + page // 2
    lens = jnp.asarray(_lens(edge, page, smax, win_sel), jnp.int32)
    b = lens.shape[0]
    npool = 1 + b * maxp            # page 0 reserved scratch, no sharing
    ks = jax.random.split(jax.random.PRNGKey(hash(case) % (2 ** 31)), 4)
    q = jax.random.normal(ks[0], (b, h, dh), dtype)
    kp = jax.random.normal(ks[1], (npool, page, hk, dh), dtype)
    vp = jax.random.normal(ks[2], (npool, page, hk, dh), dtype)
    # disjoint per-row page ranges so poisoning "unowned" is well-defined
    pt = jnp.arange(1, 1 + b * maxp, dtype=jnp.int32).reshape(b, maxp)
    out = paged_decode_attention(q, kp, vp, pt, lens, window=window,
                                 pages_per_block=pages_per_block,
                                 interpret=True)
    oracle = ref.paged_decode_attention_ref(
        q.astype(jnp.float32), kp.astype(jnp.float32),
        vp.astype(jnp.float32), pt, lens, window=window)
    tol = _tol(dtype)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(oracle), rtol=tol, atol=tol)
    return out, (q, kp, vp, pt, lens, window)


@pytest.mark.slow
@pytest.mark.parametrize("case", PAGED_AXES)
def test_paged_matrix_full(case):
    _run_paged(case)


@pytest.mark.parametrize("case", _subsample(PAGED_AXES, 14, seed=0x5EED) +
                         RAGGED_CASES)
def test_paged_matrix_sample(case):
    _run_paged(case)


@pytest.mark.parametrize("ppb", [1, 2, 3, 4, 8])
def test_paged_ppb_is_pure_schedule(ppb):
    """Every pages-per-block choice computes the same attention (each
    checked against the oracle on identical inputs)."""
    _run_paged((8, 64, 2, 1, jnp.float32, "max"), pages_per_block=ppb)


def test_paged_tuned_ppb_table_sane():
    for page, dh, hk, itemsize in itertools.product(
            (8, 16, 32, 64), (64, 128, 256), (1, 2, 4, 8), (2, 4)):
        ppb = tuned_pages_per_block(page, dh, hk, itemsize)
        assert ppb >= 1, (page, dh, hk, itemsize)
        # a block holds whole pages (every KV head); K and V each have
        # two buffers, and all four must fit the VMEM cap
        buffers, kv = 2, 2
        page_bytes = page * hk * dh * itemsize
        assert ppb * page_bytes * buffers * kv <= 2 * 1024 * 1024, \
            (page, dh, hk, itemsize, ppb)


def test_paged_ignores_unowned_pool_pages_bitwise():
    """NaN-poison every pool page outside the rows' own table ranges
    (incl. beyond each row's last *valid* page): output must be bitwise
    identical — the index-map clamp never touches foreign pages."""
    case = (8, 64, 2, 0, jnp.float32, "page-1")
    out_clean, (q, kp, vp, pt, lens, window) = _run_paged(case)
    page = kp.shape[1]
    owned = set()
    for r in range(pt.shape[0]):
        n_pages = -(-int(lens[r]) // page)
        owned |= {int(p) for p in np.asarray(pt[r, :n_pages])}
    poison = np.asarray(kp).copy()
    poison_v = np.asarray(vp).copy()
    for p in range(kp.shape[0]):
        if p not in owned:
            poison[p] = np.nan
            poison_v[p] = np.nan
    out_poison = paged_decode_attention(q, jnp.asarray(poison),
                                        jnp.asarray(poison_v), pt, lens,
                                        window=window, interpret=True)
    np.testing.assert_array_equal(np.asarray(out_clean),
                                  np.asarray(out_poison))


def test_paged_ignores_table_slots_past_live_bitwise():
    """Point every table slot past a row's live pages at another row's
    live page, and NaN-poison every pool page no row owns: the output must
    be bitwise identical to the clean run, so those slots are never read
    (nor anything they point at, which a masked read would hide)."""
    case = (8, 128, 2, 1, jnp.float32, "ragged", 2, 2)
    out_clean, (q, kp, vp, pt, lens, window) = _run_paged(case)
    page = kp.shape[1]
    pt_np = np.asarray(pt).copy()
    live = [-(-int(n) // page) for n in np.asarray(lens)]
    owned = {int(pt_np[r, j]) for r in range(len(live))
             for j in range(live[r])}
    for r in range(len(live)):
        others = [int(pt_np[o, j]) for o in range(len(live)) if o != r
                  for j in range(live[o])]
        for j in range(live[r], pt_np.shape[1]):
            pt_np[r, j] = others[j % len(others)]
    poison = np.asarray(kp).copy()
    poison_v = np.asarray(vp).copy()
    for p in range(kp.shape[0]):
        if p not in owned:
            poison[p] = np.nan
            poison_v[p] = np.nan
    out_poison = paged_decode_attention(q, jnp.asarray(poison),
                                        jnp.asarray(poison_v),
                                        jnp.asarray(pt_np), lens,
                                        window=window, pages_per_block=2,
                                        interpret=True)
    np.testing.assert_array_equal(np.asarray(out_clean),
                                  np.asarray(out_poison))


# ---------------------------------------------------------------- flash --


def _run_flash(case):
    dh, g, window, dtype, seq = case
    hk = 2
    h = hk * g
    b = 2
    ks = jax.random.split(jax.random.PRNGKey(hash(case) % (2 ** 31)), 3)
    q = jax.random.normal(ks[0], (b, seq, h, dh), dtype)
    k = jax.random.normal(ks[1], (b, seq, hk, dh), dtype)
    v = jax.random.normal(ks[2], (b, seq, hk, dh), dtype)
    out = flash_attention_pallas(q, k, v, causal=True, window=window,
                                 q_blk=32, kv_blk=32, interpret=True)
    oracle = ref.flash_attention_ref(q.astype(jnp.float32),
                                     k.astype(jnp.float32),
                                     v.astype(jnp.float32),
                                     causal=True, window=window,
                                     q_chunk=32, kv_chunk=32)
    tol = _tol(dtype)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(oracle), rtol=tol, atol=tol)


@pytest.mark.slow
@pytest.mark.parametrize("case", FLASH_AXES)
def test_flash_matrix_full(case):
    _run_flash(case)


@pytest.mark.parametrize("case", _subsample(FLASH_AXES, 10, seed=0xF1A5))
def test_flash_matrix_sample(case):
    _run_flash(case)
