"""SGMV serving edge cases + the typed admission API.

Regression tests for the three ``sgmv_apply`` hazards the multi-LoRA hot
path exposed (N=1 degenerate sort, zero-row adapters, out-of-range
adapter ids corrupting OTHER rows via scatter-destination collisions),
plus the ``ServeRequest``/nested-``ServingConfig`` API boundary: name
resolution, unknown-adapter rejection at admission, and the legacy
tuple/flat-kwarg back-compat shims.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.sgmv.ops import sgmv_apply
from repro.kernels.sgmv.ref import sgmv_ref
from repro.serverless.batching import Request
from repro.serving import (AdapterConfig, DecodeConfig, PrefillConfig,
                           ServeRequest, ServingConfig)

from conftest import make_runtime


def _rand(R=12, D=32, r=4, O=24, N=3, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(ks[0], (R, D), jnp.float32)
    a = jax.random.normal(ks[1], (N, D, r), jnp.float32) * 0.2
    b = jax.random.normal(ks[2], (N, r, O), jnp.float32) * 0.2
    return x, a, b


# ------------------------------------------------------- sgmv edge cases
@pytest.mark.parametrize("R", [1, 5, 8, 13])
def test_sgmv_n1_degenerate_matches_ref(R):
    """N=1 skips the sort entirely (identity permutation, every block is
    adapter 0) — the fast path the one-runtime-per-adapter baselines of
    bench_multi_lora ride; it must match the gather oracle including when
    R is not a row_block multiple."""
    x, a, b = _rand(R=R, N=1, seed=R)
    idx = jnp.zeros((R,), jnp.int32)
    out = sgmv_apply(x, a, b, idx, row_block=8, use_kernel=True)
    ref = sgmv_ref(x, a, b, idx)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)


def test_sgmv_zero_row_adapters_empty_segments():
    """Adapters with NO rows in the batch get zero-width padded segments —
    their (empty) blocks must not read garbage into neighbours.  Batch
    hits only adapters {0, 3} of N=5."""
    x, a, b = _rand(R=16, N=5, seed=7)
    idx = jnp.array([0, 3] * 8, jnp.int32)
    out = sgmv_apply(x, a, b, idx, row_block=8, use_kernel=True)
    ref = sgmv_ref(x, a, b, idx)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("use_kernel", [True, False, None])
def test_sgmv_out_of_range_idx_is_zero_delta_and_no_corruption(use_kernel):
    """Out-of-range ids (unloaded bank slots, garbage decode rows) must
    contribute a ZERO delta and leave in-range rows bitwise-untouched.
    Before the sanitize+mask guard, an oob id shifted the sort's segment
    offsets and CORRUPTED other rows via scatter destination collisions
    (observed max diff ~8.5 on valid rows with idx in {5, 7}, N=4)."""
    x, a, b = _rand(R=12, N=4, seed=3)
    good = jnp.array([0, 1, 2, 3] * 3, jnp.int32)
    bad = good.at[2].set(7).at[5].set(-1).at[9].set(4)
    out_bad = np.asarray(sgmv_apply(x, a, b, bad, row_block=8,
                                    use_kernel=use_kernel))
    out_good = np.asarray(sgmv_apply(x, a, b, good, row_block=8,
                                     use_kernel=use_kernel))
    oob = np.asarray([i in (2, 5, 9) for i in range(12)])
    # oob rows: exactly zero (not NaN, not adapter-0 spill)
    np.testing.assert_array_equal(out_bad[oob], 0.0)
    # in-range rows: bitwise identical to the all-valid batch
    np.testing.assert_array_equal(out_bad[~oob], out_good[~oob])


def test_sgmv_auto_dispatch_off_tpu_is_the_reference():
    """use_kernel=None (the serving default) resolves to the gather-BMM
    reference off TPU — bitwise, so CPU replays and the single-adapter
    oracle runtimes of bench_multi_lora produce identical bits."""
    x, a, b = _rand(seed=11)
    idx = jnp.array([2, 0, 1] * 4, jnp.int32)
    auto = np.asarray(sgmv_apply(x, a, b, idx))
    ref = np.asarray(sgmv_apply(x, a, b, idx, use_kernel=False))
    np.testing.assert_array_equal(auto, ref)


# ------------------------------------------------- typed admission API
@pytest.fixture(scope="module")
def runtime(llama_model):
    cfg, params = llama_model
    return make_runtime(cfg, params)


def _req(rid, out=2):
    return Request(req_id=rid, fn_id="fn0", arrival=0.0, prompt_len=12,
                   output_len=out, slo_ttft=10.0)


def _drain(rt):
    while rt.slots.num_active:
        rt.decode()


def test_admission_rejects_out_of_range_adapter(runtime):
    """An adapter slot outside the bank must be rejected AT ADMISSION
    (counted + breakdown-flagged), not silently served as a zero/garbage
    delta at decode.  The in-range groupmate is still admitted."""
    rt = runtime
    rng = np.random.default_rng(0)
    before = rt.stats["rejected_unknown_adapter"]
    bad, good = _req(100), _req(101)
    res = rt.try_admit([
        ServeRequest(prompt=rng.integers(0, 64, 12, dtype=np.int32),
                     adapter=7, request=bad),
        ServeRequest(prompt=rng.integers(0, 64, 12, dtype=np.int32),
                     adapter=2, request=good),
    ])
    assert res is not None
    assert [r.req_id for r in res.rejected] == [100]
    assert bad.breakdown["rejected_unknown_adapter"] == 1.0
    assert rt.stats["rejected_unknown_adapter"] == before + 1
    assert len(res.slot_ids) == 1          # aligned with survivors
    _drain(rt)
    assert rt.pool.in_use == 0


def test_admission_name_without_registry_raises(runtime):
    """Adapter NAMES need a registry — resolving a string with none
    attached is a configuration error, not a graceful rejection."""
    rt = runtime
    rng = np.random.default_rng(1)
    with pytest.raises(ValueError, match="AdapterRegistry"):
        rt.try_admit([ServeRequest(
            prompt=rng.integers(0, 64, 12, dtype=np.int32),
            adapter="summarize", request=_req(102))])


def test_legacy_tuple_admission_warns_and_still_works(runtime):
    """The (Request, prompt, adapter:int) tuple form survives one release
    behind a DeprecationWarning and behaves identically."""
    rt = runtime
    rng = np.random.default_rng(2)
    r = _req(103, out=3)
    with pytest.warns(DeprecationWarning, match="ServeRequest") as got:
        res = rt.try_admit(
            [(r, rng.integers(0, 64, 12, dtype=np.int32), 1)])
    assert got[0].filename == __file__      # attributed to the caller
    assert res is not None and len(res.slot_ids) == 1
    _drain(rt)
    assert rt.pool.in_use == 0


def test_serve_request_synthesizes_request_record():
    sr = ServeRequest(prompt=np.arange(8, dtype=np.int32), adapter=0,
                      arrival=1.5, max_new_tokens=4)
    req = sr.ensure_request()
    assert req.prompt_len == 8 and req.output_len == 4
    assert req.arrival == 1.5 and req.req_id < 0
    assert sr.ensure_request() is req      # stable across calls


# ---------------------------------------------------- ServingConfig API
def test_serving_config_flat_kwargs_match_nested():
    flat = ServingConfig(num_slots=2, prefill_chunk=64, prefill_rows=2,
                         decode_chunk=8, eos_id=5, max_live_adapters=4,
                         sgmv_kernel=False)
    nested = ServingConfig(
        num_slots=2, prefill=PrefillConfig(chunk=64, rows=2),
        decode=DecodeConfig(chunk=8, eos_id=5),
        adapters=AdapterConfig(max_live=4, sgmv_kernel=False))
    assert flat == nested
    # flat read-through views keep the old field names alive
    assert flat.prefill_chunk == 64 and flat.prefill_rows == 2
    assert flat.decode_chunk == 8 and flat.eos_id == 5
    assert flat.adapters.max_live == 4


def test_serving_config_rejects_mixed_and_unknown_kwargs():
    with pytest.raises(ValueError, match="not both"):
        ServingConfig(prefill=PrefillConfig(chunk=64), prefill_chunk=32)
    with pytest.raises(TypeError, match="unexpected keyword"):
        ServingConfig(prefil_chunk=64)     # typo must not pass silently
