"""CompileGuard: the dynamic half of the compile-once contract.

A deliberately shape-unstable dispatch must blow the budget and raise;
fixed-shape replay (every serving test runs this way now) passes under
``max_compiles=1``; budgets never mask a body exception; and the
report names every watched function."""
import jax
import jax.numpy as jnp
import pytest

from repro.serving import CompileBudgetExceeded, CompileGuard
from repro.serving.compile_guard import _COMPILE_EVENT


def test_shape_unstable_dispatch_fails():
    f = jax.jit(lambda x: x * 2.0)
    with pytest.raises(CompileBudgetExceeded, match="compiled 2x"):
        with CompileGuard({"f": 1}) as guard:
            guard.watch("f", f)
            f(jnp.zeros((4,)))
            f(jnp.zeros((8,)))      # new shape -> re-jit -> budget blown


def test_fixed_shape_replay_passes():
    f = jax.jit(lambda x: x + 1.0)
    with CompileGuard({"f": 1}) as guard:
        guard.watch("f", f)
        for _ in range(4):
            f(jnp.zeros((4,)))      # one shape, one compile
    assert guard.compiles("f") == 1


def test_baseline_excludes_prior_compiles():
    """Compiles before the watch (cold-start warmup outside the guard)
    must not count against the budget."""
    f = jax.jit(lambda x: x - 1.0)
    f(jnp.zeros((4,)))              # pre-guard warmup
    with CompileGuard({"f": 0}) as guard:
        guard.watch("f", f)
        f(jnp.zeros((4,)))          # cache hit: zero new compiles
    assert guard.compiles("f") == 0


def test_attach_watches_runtime_dispatches():
    class FakeRuntime:
        def __init__(self):
            self._decode = jax.jit(lambda x: x * 2)
            self._prefill = jax.jit(lambda x: x * 3)

    rt = FakeRuntime()
    with CompileGuard({"decode": 1, "prefill": 1}, runtime=rt) as guard:
        rt._decode(jnp.zeros((2,)))
        rt._prefill(jnp.zeros((2,)))
    rep = guard.report()
    assert rep["decode_compiles"] == 1 and rep["decode_budget"] == 1
    assert rep["prefill_compiles"] == 1 and rep["prefill_budget"] == 1
    assert "backend_compiles" in rep


def test_body_exception_not_masked():
    """A blown budget must not shadow the body's own failure — check()
    only runs on a clean exit."""
    f = jax.jit(lambda x: x / 2.0)
    with pytest.raises(RuntimeError, match="body failed"):
        with CompileGuard({"f": 0}) as guard:
            guard.watch("f", f)
            f(jnp.zeros((4,)))      # budget 0 already blown
            raise RuntimeError("body failed")


def test_unbudgeted_watch_reports_without_enforcing():
    f = jax.jit(lambda x: x * 5.0)
    with CompileGuard() as guard:   # no budgets at all
        guard.watch("f", f)
        f(jnp.zeros((2,)))
        f(jnp.zeros((6,)))          # 2 compiles, nothing enforced
    assert guard.report()["f_compiles"] == 2
    assert "f_budget" not in guard.report()


def test_max_total_counts_backend_compiles():
    with pytest.raises(CompileBudgetExceeded, match="backend compiles"):
        with CompileGuard(max_total=0):
            jax.jit(lambda x: x @ x)(jnp.zeros((3, 3)))


def test_exit_unregisters_listener():
    """After ``__exit__`` the guard's listener is gone from jax.monitoring:
    a later compile never reaches its callback, and guards do not pile up
    listeners for the life of the process."""
    from jax._src import monitoring
    before = len(monitoring.get_event_duration_listeners())
    seen = []
    guard = CompileGuard()
    guard._on_event = lambda event, duration, **kw: seen.append(event)
    with guard:
        assert len(monitoring.get_event_duration_listeners()) == before + 1
        jax.jit(lambda x: x * 7.0)(jnp.zeros((5,)))
    assert _COMPILE_EVENT in seen
    assert len(monitoring.get_event_duration_listeners()) == before
    seen.clear()
    jax.jit(lambda x: x * 11.0)(jnp.zeros((9,)))    # a fresh compile
    assert seen == []
