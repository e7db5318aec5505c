"""CompileGuard — runtime enforcement of the compile-once contract.

The static analyzer (``tools/reprolint``, RL001) catches recompile
*hazards* in source; this guard catches recompiles that actually
happen.  Any test or bench wraps its serving code in::

    with CompileGuard(max_compiles={"decode": 1, "prefill": 1},
                      runtime=rt):
        replay_trace(rt, trace)

and fails loudly — ``CompileBudgetExceeded`` — if a watched jitted
function compiled more often than its budget while the guard was
active.  A silent re-jit mid-serving is a cold start by another name
(it blows every TPOT SLO the paper's scheduling is built around).

Two measurement channels:

* **per-function** (``max_compiles``): named jitted callables
  registered via :meth:`watch` / :meth:`attach`; compile counts come
  from the jit cache-size probe (``fn._cache_size()``).
* **process-wide** (``max_total``): every XLA backend compile,
  observed via ``jax.monitoring``'s
  ``/jax/core/compile/backend_compile_duration`` event.  This counts
  *everything* (including e.g. a first ``jnp.zeros``), so it is
  opt-in; ``report()`` always includes the observed total so benches
  can print it.

The guard never perturbs what it measures: it only reads cache sizes
and listens to monitoring events.  ``metrics_snapshot()``'s
``decode_compiles``/``prefill_compiles`` gauges report the same probe
for offline artifacts (docs/observability.md); the guard is the
in-process enforcement of the same invariant.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional

import jax

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileBudgetExceeded(AssertionError):
    """A watched function compiled more often than its budget."""


class CompileGuard:
    """Context manager asserting compile budgets over its body.

    Args:
        max_compiles: budget per watched name, e.g.
            ``{"decode": 1, "prefill": 1}``.  Names without a budget
            are watched (and reported) but unchecked.
        max_total: optional budget on *process-wide* backend compiles
            while active (counts every XLA compile, not just watched
            functions).
        runtime: optional ``ContinuousRuntime``; forwarded to
            :meth:`attach` on ``__enter__``.
    """

    def __init__(self, max_compiles: Optional[Dict[str, int]] = None,
                 *, max_total: Optional[int] = None,
                 runtime: Any = None):
        self.max_compiles = dict(max_compiles or {})
        self.max_total = max_total
        self._runtime = runtime
        self._watched: Dict[str, Any] = {}
        self._baseline: Dict[str, int] = {}
        self.backend_compiles = 0
        self.backend_compile_seconds = 0.0
        self._listener: Optional[Callable] = None
        self._active = False

    # -- registration -----------------------------------------------
    def watch(self, name: str, fn: Any) -> "CompileGuard":
        """Watch a jitted callable; baseline is its current cache size
        (so only compiles that happen *inside* the guard count)."""
        self._watched[name] = fn
        self._baseline[name] = fn._cache_size()
        return self

    def attach(self, runtime: Any) -> "CompileGuard":
        """Watch a ContinuousRuntime's decode + prefill dispatches."""
        return self.watch("decode", runtime._decode) \
                   .watch("prefill", runtime._prefill)

    # -- measurement ------------------------------------------------
    def compiles(self, name: str) -> int:
        """New compiles of ``name`` since it was watched."""
        return self._watched[name]._cache_size() - self._baseline[name]

    def report(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {
            "backend_compiles": self.backend_compiles,
            "backend_compile_seconds": self.backend_compile_seconds,
        }
        for name in self._watched:
            out[f"{name}_compiles"] = self.compiles(name)
            if name in self.max_compiles:
                out[f"{name}_budget"] = self.max_compiles[name]
        return out

    def check(self) -> None:
        """Raise CompileBudgetExceeded on any blown budget."""
        for name, budget in self.max_compiles.items():
            if name not in self._watched:
                continue
            n = self.compiles(name)
            if n > budget:
                raise CompileBudgetExceeded(
                    f"'{name}' compiled {n}x inside CompileGuard "
                    f"(budget {budget}) — a re-jit mid-serving is a "
                    f"cold start by another name; every dispatch "
                    f"shape/dtype must be fixed")
        if self.max_total is not None \
                and self.backend_compiles > self.max_total:
            raise CompileBudgetExceeded(
                f"{self.backend_compiles} backend compiles inside "
                f"CompileGuard (budget {self.max_total})")

    # -- context manager --------------------------------------------
    def _on_event(self, event: str, duration: float,
                  **kwargs: Any) -> None:
        if event == _COMPILE_EVENT and self._active:
            self.backend_compiles += 1
            self.backend_compile_seconds += duration

    def __enter__(self) -> "CompileGuard":
        if self._runtime is not None:
            self.attach(self._runtime)
        self._listener = self._on_event
        jax.monitoring.register_event_duration_secs_listener(self._listener)
        self._active = True
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self._active = False
        if self._listener is not None:
            jax.monitoring.unregister_event_duration_listener(self._listener)
            self._listener = None
        if exc_type is None:
            self.check()
