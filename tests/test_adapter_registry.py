"""Adapter lifecycle against a LIVE runtime: load -> serve -> unload ->
slot reuse, pin semantics, prefix-cache purge on unload, zero re-jit
across churn, and the mixed-adapter-vs-single-adapter bitwise oracle.
"""
import jax
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.core.lora import combine_lora, partition_lora, random_adapter
from repro.models import transformer as tf
from repro.serving import (AdapterRegistry, CompileGuard, ContinuousRuntime,
                           ServeRequest, ServingConfig)

BS = 8


@pytest.fixture(scope="module")
def model():
    cfg = get_smoke("llama2_7b").with_(dtype="float32")
    params = tf.init_params(jax.random.PRNGKey(0), cfg, lora_adapters=3)
    return cfg, params


def _mk_rt(cfg, params, **kw):
    scfg = ServingConfig(num_slots=4, block_size=BS, num_blocks=32,
                         max_blocks_per_slot=6, prefill_chunk=16,
                         decode_chunk=4, **kw)
    return ContinuousRuntime(cfg, params, scfg)


def _serve(rt, items):
    """Admit [(prompt, adapter, out)] and run to completion; returns the
    per-item full token lists (first token + decode emissions)."""
    srs = [ServeRequest(prompt=p, adapter=a, max_new_tokens=o)
           for p, a, o in items]
    res = rt.try_admit(srs)
    assert res is not None and not res.rejected
    toks = {i: [res.first_tokens[i]] for i in range(len(items))}
    sid2i = {sid: i for i, sid in enumerate(res.slot_ids) if sid >= 0}
    while rt.slots.num_active:
        d = rt.decode()
        for sid, t in d.emitted.items():
            if sid in sid2i:
                toks[sid2i[sid]].extend(t)
    return [toks[i] for i in range(len(items))]


def _single_adapter_params(params, slot):
    """Slice ONE bank slot into an N=1 bank (the one-runtime-per-adapter
    oracle's params: same backbone arrays, bank capacity 1)."""
    bb, bank = partition_lora(params)
    one = jax.tree_util.tree_map(
        lambda x: None if x is None else
        jax.lax.slice_in_dim(x, slot, slot + 1, axis=-3),
        bank, is_leaf=lambda x: x is None)
    return combine_lora(bb, one)


# ------------------------------------------------------------- lifecycle
def test_load_serve_unload_slot_reuse_roundtrip(model):
    cfg, params = model
    rt = _mk_rt(cfg, params)
    reg = AdapterRegistry(rt)
    assert rt.adapters is reg and reg.capacity == 3
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab_size, 12, dtype=np.int32)

    assert reg.load("summarize", random_adapter(params, 1)) == 0
    assert reg.load("translate", random_adapter(params, 2)) == 1
    out_a = _serve(rt, [(prompt, "summarize", 4)])[0]
    assert len(out_a) >= 4

    reg.unload("summarize")
    assert reg.names() == ["translate"]
    # the freed slot is recycled LIFO for the next tenant
    assert reg.load("classify", random_adapter(params, 3)) == 0
    out_c = _serve(rt, [(prompt, "classify", 4)])[0]
    assert len(out_c) >= 4
    # different weights in the same slot -> different tokens (the slot is
    # a container, not an identity)
    assert out_a != out_c

    # the unloaded name is gone: graceful rejection, not a zero delta
    res = rt.try_admit([ServeRequest(prompt=prompt, adapter="summarize",
                                     max_new_tokens=2)])
    assert len(res.rejected) == 1
    assert rt.stats["rejected_unknown_adapter"] >= 1
    assert rt.stats["adapter_loads"] == 3
    assert rt.stats["adapter_unloads"] == 1
    assert rt.pool.in_use == 0 and rt.slots.num_active == 0


def test_unload_while_pinned_refused(model):
    cfg, params = model
    rt = _mk_rt(cfg, params)
    reg = AdapterRegistry(rt)
    reg.load("live", random_adapter(params, 5))
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, cfg.vocab_size, 12, dtype=np.int32)
    res = rt.try_admit([ServeRequest(prompt=prompt, adapter="live",
                                     max_new_tokens=8)])
    assert res.slot_ids[0] >= 0            # bound, still decoding
    assert reg.pinned("live") == 1
    with pytest.raises(RuntimeError, match="pin"):
        reg.unload("live")
    with pytest.raises(RuntimeError, match="pin"):
        reg.swap("live", random_adapter(params, 6))
    while rt.slots.num_active:
        rt.decode()
    assert reg.pinned("live") == 0         # finish unpins
    reg.unload("live")                     # now legal
    assert len(reg) == 0


def test_unload_purges_prefix_cache(model):
    """The trie is adapter-keyed: once a slot is unloaded its indexed
    prompt blocks MUST become unmatchable (a future tenant of the slot
    would otherwise hit K/V computed under the old weights)."""
    cfg, params = model
    rt = _mk_rt(cfg, params)
    reg = AdapterRegistry(rt)
    slot = reg.load("fn", random_adapter(params, 9))
    rng = np.random.default_rng(2)
    prompt = rng.integers(0, cfg.vocab_size, 2 * BS, dtype=np.int32)

    _serve(rt, [(prompt, "fn", 2)])
    assert len(rt.prefix) > 0              # full prompt blocks indexed
    assert rt.pool.num_cached > 0          # parked for reuse
    assert rt.prefix.covered_tokens(slot, prompt) == 2 * BS

    # sanity: a re-serve WOULD have shared (the stale-hit hazard is real)
    reg.unload("fn")
    assert len(rt.prefix) == 0
    assert rt.pool.num_cached == 0         # parked blocks went back free
    assert rt.prefix.covered_tokens(slot, prompt) == 0

    # same slot, new tenant, same prompt: nothing shared, no stale K/V
    reg.load("fn2", random_adapter(params, 10))
    shared_before = rt.stats["shared_tokens"]
    _serve(rt, [(prompt, "fn2", 2)])
    assert rt.stats["shared_tokens"] == shared_before


# ------------------------------------- churn compile-once + bitwise oracle
def test_adapter_churn_zero_rejit_and_bitwise_oracle(model):
    """Mixed-adapter serving with load/unload churn between dispatches:
    decode and prefill each compile EXACTLY once (the adapter vector is
    data, not shape), and every request's tokens are bitwise-identical to
    a one-runtime-per-adapter oracle (N=1 bank slices)."""
    cfg, params = model
    rt = _mk_rt(cfg, params)
    reg = AdapterRegistry(rt)
    rng = np.random.default_rng(3)
    p1 = rng.integers(0, cfg.vocab_size, 12, dtype=np.int32)
    p2 = rng.integers(0, cfg.vocab_size, 14, dtype=np.int32)

    reg.load("a", random_adapter(params, 21))
    reg.load("b", random_adapter(params, 22))
    with CompileGuard({"decode": 1, "prefill": 1}, runtime=rt):
        rt.warmup()
        # both adapters live in ONE decode batch
        mixed = _serve(rt, [(p1, "a", 6), (p2, "b", 6)])
        # churn: swap weights in, unload, load a new tenant — zero re-jit
        reg.load("c", random_adapter(params, 23))
        out_c = _serve(rt, [(p1, "c", 6)])[0]
        reg.unload("b")
        reg.load("d", random_adapter(params, 24))
        mixed2 = _serve(rt, [(p1, "a", 6), (p2, "d", 6)])
    assert rt.decode_compiles() == 1
    assert rt.prefill_compiles() == 1

    # oracle: each request replayed alone on an N=1-bank runtime built
    # from the SAME post-churn params — bitwise token equality
    for prompt, name, want in [(p1, "a", mixed[0]), (p2, "d", mixed2[1]),
                               (p1, "c", out_c), (p1, "a", mixed2[0])]:
        single = ContinuousRuntime(
            cfg, _single_adapter_params(rt.params, reg.slot_of(name)),
            rt.scfg)
        got = _serve(single, [(prompt, 0, 6)])[0]
        assert got == want, f"{name}: mixed {want} != single-runtime {got}"

    # adapters genuinely differ (b != 0): the comparison is not vacuous
    assert mixed[0] != mixed[1]