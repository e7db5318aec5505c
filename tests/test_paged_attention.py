"""Paged-attention decode kernel vs oracle: the in-kernel bounded walk
(interpret=True on CPU) — dead blocks never read, rows ending mid compute
step, tables not a multiple of the pages per step, reclaimed window
entries — the fused jnp fallback, mixed live/stalled/inactive rows,
ragged per-row positions, window masking and garbage-block isolation."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import get_kernel
from repro.kernels.paged_attention.ops import (_paged_decode_jnp,
                                               paged_decode_gqa)
from repro.kernels import largest_divisor_block
from repro.kernels.paged_attention.paged_attn import (_pages_per_step,
                                                      paged_decode_attention)
from repro.kernels.paged_attention.ref import paged_attention_ref


def _mk(B, K, G, hd, bs, MB, NB, seed=0, dtype=jnp.float32, *,
        inactive_rows=(), stalled_rows=()):
    """Random pools + a mixed-state block table.

    Active rows get a random number of allocated blocks and a ragged
    position inside their last block; ``inactive_rows`` are all -1 (free
    decode slots); ``stalled_rows`` sit at pos 0 with one block (a slot
    replaying its pending token)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, K * G, hd), jnp.float32).astype(dtype)
    kp = jax.random.normal(ks[1], (K, NB, bs, hd), jnp.float32).astype(dtype)
    vp = jax.random.normal(ks[2], (K, NB, bs, hd), jnp.float32).astype(dtype)
    rng = np.random.default_rng(seed)
    tbl = np.full((B, MB), -1, np.int32)
    pos = np.zeros((B,), np.int32)
    for b in range(B):
        if b in inactive_rows:
            continue
        nb = 1 if b in stalled_rows else int(rng.integers(1, MB + 1))
        tbl[b, :nb] = rng.choice(np.arange(1, NB), size=nb, replace=False)
        pos[b] = 0 if b in stalled_rows else \
            int(rng.integers((nb - 1) * bs, nb * bs))
    return q, kp, vp, jnp.asarray(tbl), jnp.asarray(pos)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,K,G,hd,bs,MB,NB,win", [
    (4, 2, 4, 64, 16, 8, 32, None),
    (3, 2, 3, 32, 8, 6, 24, 4),        # sliding window
    (2, 4, 1, 64, 16, 4, 16, None),    # MHA (G=1)
    (2, 1, 2, 16, 48, 4, 16, None),    # bs=48: 5 pages a step, MB 4
])
def test_kernel_matches_oracle_interpret(B, K, G, hd, bs, MB, NB, win,
                                         dtype):
    q, kp, vp, tbl, pos = _mk(B, K, G, hd, bs, MB, NB, seed=B + bs,
                              dtype=dtype)
    ref = paged_attention_ref(q, kp, vp, tbl, pos, window=win)
    out = paged_decode_gqa(q, kp, vp, tbl, pos, window=win, interpret=True)
    tol = 3e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol, rtol=tol)


def test_fused_jnp_matches_oracle():
    """The off-TPU fast path (what the serving runtime runs on CPU)."""
    for win in (None, 6):
        q, kp, vp, tbl, pos = _mk(5, 2, 2, 32, 8, 8, 32, seed=11)
        ref = paged_attention_ref(q, kp, vp, tbl, pos, window=win)
        out = _paged_decode_jnp(q, kp, vp, tbl, pos, window=win)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=3e-5, rtol=3e-5)


def test_bounded_walk_bitwise_equals_full_walk():
    """The decode walk bounded by the group's max live block count
    (``max(pos) // bs + 1`` — the ROADMAP carry-over PR 5 left open)
    must be BITWISE equal to walking the full table capacity: pruned
    blocks are fully masked, so with exact-zero masked keys every
    skipped step is a strict float identity, not an approximation.
    Mixed occupancy (ragged rows + inactive rows) is the hard case."""
    for seed, win, inactive in ((11, None, ()), (23, 6, (0, 3))):
        q, kp, vp, tbl, pos = _mk(5, 2, 2, 32, 8, 8, 32, seed=seed,
                                  inactive_rows=inactive)
        bounded = _paged_decode_jnp(q, kp, vp, tbl, pos, window=win)
        full = _paged_decode_jnp(q, kp, vp, tbl, pos, window=win,
                                 full_walk=True)
        np.testing.assert_array_equal(np.asarray(bounded),
                                      np.asarray(full))
    # the bound actually bites: a one-block row among empties must not
    # walk all MB blocks — proof by equality when the rest of the pool
    # is poisoned with NaNs at block indices the bounded walk never
    # touches (a full walk would clip -1 -> block 0 and read them fine,
    # but any misindexed bounded read would surface as NaN)
    q, kp, vp, tbl, pos = _mk(4, 2, 2, 32, 8, 8, 32, seed=31,
                              inactive_rows=(1, 2, 3), stalled_rows=(0,))
    assert int(jnp.max(pos)) == 0          # one live block in the group
    out = _paged_decode_jnp(q, kp, vp, tbl, pos)
    assert np.isfinite(np.asarray(out)).all()


def test_mixed_live_stalled_inactive_rows():
    """Inactive (-1 table) and stalled (pos=0) rows must not perturb live
    rows, and every row's output must stay finite (branch-free batch)."""
    B = 6
    q, kp, vp, tbl, pos = _mk(B, 2, 2, 32, 8, 6, 32, seed=3,
                              inactive_rows=(1, 4), stalled_rows=(2,))
    ref = paged_attention_ref(q, kp, vp, tbl, pos)
    out = paged_decode_gqa(q, kp, vp, tbl, pos, interpret=True)
    live = [b for b in range(B) if b not in (1, 4)]
    np.testing.assert_allclose(np.asarray(out)[live], np.asarray(ref)[live],
                               atol=3e-5, rtol=3e-5)
    assert np.isfinite(np.asarray(out)).all()


def test_garbage_block_isolation():
    """Scribbling over the garbage block (where -1 entries clip) and over
    unreferenced pool blocks must not change any live row's output."""
    q, kp, vp, tbl, pos = _mk(4, 2, 2, 32, 8, 6, 32, seed=7,
                              inactive_rows=(3,))
    base = paged_decode_gqa(q, kp, vp, tbl, pos, interpret=True)
    used = set(np.asarray(tbl)[np.asarray(tbl) >= 0].tolist())
    unused = [i for i in range(32) if i not in used and i != 0]
    kp2 = kp.at[:, [0] + unused[:3]].set(99.0)
    vp2 = vp.at[:, [0] + unused[:3]].set(-99.0)
    out = paged_decode_gqa(q, kp2, vp2, tbl, pos, interpret=True)
    live = [0, 1, 2]
    np.testing.assert_allclose(np.asarray(out)[live],
                               np.asarray(base)[live], atol=1e-6)


def test_ragged_positions_row_equivalence():
    """Per-row positions must behave exactly like B independent single-row
    calls (the property scalar-``pos`` decode_attention cannot express)."""
    B = 4
    q, kp, vp, tbl, pos = _mk(B, 2, 2, 32, 8, 6, 32, seed=13)
    out = paged_decode_gqa(q, kp, vp, tbl, pos, interpret=True)
    for b in range(B):
        row = paged_decode_gqa(q[b:b + 1], kp, vp, tbl[b:b + 1],
                               pos[b:b + 1], interpret=True)
        np.testing.assert_allclose(np.asarray(out)[b], np.asarray(row)[0],
                                   atol=3e-5, rtol=3e-5)


def test_sub_block_rule():
    """Blocks are never split: one compute step fetches whole pages, about
    256 keys' worth (at least one, at most the table), and the result does
    not depend on where the steps fall.  The shared largest-divisor rule
    the other kernels tile with stays as it was."""
    assert largest_divisor_block(768, 512) == 384
    assert largest_divisor_block(96, 64) == 48
    assert largest_divisor_block(16, 512) == 16
    assert largest_divisor_block(48, 32) == 24
    assert largest_divisor_block(7, 4) == 1
    assert _pages_per_step(16, 256) == 16
    assert _pages_per_step(16, 8) == 8
    assert _pages_per_step(48, 256) == 5
    assert _pages_per_step(512, 4) == 1
    # bs=48, MB 4: one step covers the whole table; each row equals its
    # own one-row call
    q, kp, vp, tbl, pos = _mk(2, 1, 2, 16, 48, 4, 16, seed=5)
    whole = paged_decode_attention(q.reshape(2, 1, 2, 16), kp, vp, tbl, pos,
                                   interpret=True)
    for b in range(2):
        row = paged_decode_attention(q[b:b + 1].reshape(1, 1, 2, 16), kp, vp,
                                     tbl[b:b + 1], pos[b:b + 1],
                                     interpret=True)
        np.testing.assert_allclose(np.asarray(whole)[b], np.asarray(row)[0],
                                   atol=3e-5, rtol=3e-5)


# the chat cell's attention shapes (Qwen2.5-3B: 2 kv heads of 8 query heads,
# hd 128, 16-token blocks) at a small batch; MB 40 is not a multiple of the
# 16 pages a compute step fetches
CELL = dict(K=2, G=8, hd=128, bs=16, MB=40, NB=160)


def _cell_rows(dtype, window=None, seed=0):
    """Rows in every state the runtime produces, at the cell's shapes:
    live rows ending mid compute step, at a step's edge and in the
    table's last entry; a windowed row whose blocks before its window
    were reclaimed (-1); an inactive slot (all -1, pos 0) and one with a
    stale position; a stalled row (pos 0, one block).  Returns the inputs
    and each live row's (first, last) logical block."""
    K, G, hd, bs, MB, NB = (CELL[k] for k in ("K", "G", "hd", "bs", "MB",
                                              "NB"))
    live_pos = [16 * 20 + 5, 16 * 16 - 1, 16 * 39 + 9, 16 * 33 + 2, 0]
    B = len(live_pos) + 2
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, K * G, hd), jnp.float32).astype(dtype)
    kp = jax.random.normal(ks[1], (K, NB, bs, hd), jnp.float32).astype(dtype)
    vp = jax.random.normal(ks[2], (K, NB, bs, hd), jnp.float32).astype(dtype)
    rng = np.random.default_rng(seed)
    phys = iter(rng.permutation(np.arange(1, NB)).tolist())
    tbl = np.full((B, MB), -1, np.int32)
    pos = np.zeros((B,), np.int32)
    ranges = {}
    for b, p in enumerate(live_pos):
        last = p // bs
        tbl[b, :last + 1] = [next(phys) for _ in range(last + 1)]
        pos[b] = p
        first = 0 if window is None else max(0, p - window + 1) // bs
        if b == 3 and window is not None:
            tbl[b, :first] = -1                      # reclaimed
        ranges[b] = (first, last)
    pos[B - 1] = 300                                 # inactive, stale pos
    return (q, kp, vp, jnp.asarray(tbl), jnp.asarray(pos)), ranges


def _poison_dead_blocks(kp, vp, tbl, ranges):
    """NaN in every pool block outside each live row's walk, block 0 (where
    -1 entries used to clip) included."""
    t = np.asarray(tbl)
    keep = {int(t[b, j]) for b, (f, l) in ranges.items()
            for j in range(f, l + 1) if t[b, j] >= 0}
    dead = np.array([i for i in range(kp.shape[1]) if i not in keep])
    return kp.at[:, dead].set(jnp.nan), vp.at[:, dead].set(jnp.nan)


@pytest.mark.parametrize("window", [None, 100])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_dead_blocks_never_read(dtype, window):
    """The walk reads only each row's live blocks: with NaN in every other
    pool block, each live row still matches the oracle (computed on the
    clean pools) and every row, inactive ones too, stays finite.  A walk
    that fetched past a row's position, a -1 entry or a block before the
    window would turn the row NaN (0 weight times a NaN value is NaN)."""
    (q, kp, vp, tbl, pos), ranges = _cell_rows(dtype, window)
    ref = paged_attention_ref(q, kp, vp, tbl, pos, window=window)
    kp_bad, vp_bad = _poison_dead_blocks(kp, vp, tbl, ranges)
    out = paged_decode_gqa(q, kp_bad, vp_bad, tbl, pos, window=window,
                           interpret=True)
    assert np.isfinite(np.asarray(out, np.float32)).all()
    live = sorted(ranges)
    tol = 3e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32)[live],
                               np.asarray(ref, np.float32)[live],
                               atol=tol, rtol=tol)


def test_row_past_its_allocation_walks_nothing():
    """A row whose own block is unallocated — an inactive slot, or a scan
    step past the row's allocation, whose output the runtime discards —
    walks nothing and writes zeros; the other rows are unaffected."""
    (q, kp, vp, tbl, pos), _ = _cell_rows(jnp.float32)
    t = np.asarray(tbl).copy()
    t[0, pos[0] // CELL["bs"]] = -1                   # earlier blocks held
    out = paged_decode_gqa(q, kp, vp, jnp.asarray(t), pos, interpret=True)
    ref = paged_attention_ref(q, kp, vp, tbl, pos)
    np.testing.assert_array_equal(np.asarray(out)[[0, 5, 6]], 0.0)
    np.testing.assert_allclose(np.asarray(out)[1:5], np.asarray(ref)[1:5],
                               atol=3e-5, rtol=3e-5)


def test_ops_dispatch_and_registry():
    q, kp, vp, tbl, pos = _mk(3, 2, 2, 32, 8, 4, 16, seed=9)
    ref = paged_attention_ref(q, kp, vp, tbl, pos)
    # use_kernel=False IS the reference
    np.testing.assert_array_equal(
        np.asarray(paged_decode_gqa(q, kp, vp, tbl, pos, use_kernel=False)),
        np.asarray(ref))
    # auto dispatch (fused jnp on CPU / Pallas on TPU) agrees with it
    out = paged_decode_gqa(q, kp, vp, tbl, pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=3e-5, rtol=3e-5)
    # registry resolves to the same entry point
    fn = get_kernel("paged_attention")
    np.testing.assert_array_equal(
        np.asarray(fn(q, kp, vp, tbl, pos, use_kernel=False)),
        np.asarray(ref))
    with pytest.raises(KeyError):
        get_kernel("nonexistent_kernel")
