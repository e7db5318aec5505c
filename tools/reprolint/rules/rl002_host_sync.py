"""RL002 — host sync inside the plan region.

The serving loop's throughput rests on JAX async dispatch: the host
plans admission, block allocation, and chunk scheduling for batch N+1
while the device still runs batch N.  Any device->host materialization
inside that planning code — ``np.asarray``/``np.array`` on a device
array, ``jax.device_get``, ``.block_until_ready()``, or ``float()``
over a dispatch result — stalls the host until the device drains,
serializing the pipeline: in a profiler trace the device then idles
under whichever ``runtime.*`` span the host is in
(docs/observability.md).

The *plan region* is the set of scheduler methods configured via
``plan-functions`` (``[tool.reprolint]``), by default the
``ContinuousRuntime`` planning/dispatch methods.  A serving step needs
exactly one sync per emitted token batch; those deliberate syncs are
annotated ``# reprolint: sync-point`` and everything else is a bug.
Syncs inside a Python loop get an extra warning: that is one full
pipeline stall *per iteration*.
"""
from __future__ import annotations

import ast
from typing import List, Set

from tools.reprolint.core import FuncInfo, ProjectIndex, Violation

_SYNC_CALLS = {"numpy.asarray", "numpy.array", "numpy.copy",
               "jax.device_get"}
_CAST_OVER_DISPATCH = {"int", "float"}


def _loop_nodes(fi: FuncInfo) -> Set[int]:
    inside: Set[int] = set()
    for node in fi.walk():
        if isinstance(node, (ast.For, ast.While)):
            for sub in ast.walk(node):
                if sub is not node:
                    inside.add(id(sub))
    return inside


def check(index: ProjectIndex, cfg) -> List[Violation]:
    out: List[Violation] = []
    plan_funcs = [fi for f in index.files for fi in f.funcs
                  if cfg.is_plan_function(fi.qualified())]
    for fi in plan_funcs:
        in_loop = _loop_nodes(fi)
        for node in fi.walk():
            if not isinstance(node, ast.Call):
                continue
            msg = ""
            fn = node.func
            dotted = index.resolve_dotted(fn, fi.scope)
            if dotted in _SYNC_CALLS:
                msg = f"`{dotted}` syncs device->host"
            elif isinstance(fn, ast.Attribute) \
                    and fn.attr == "block_until_ready":
                msg = "`.block_until_ready()` stalls the host"
            elif isinstance(fn, ast.Name) \
                    and fn.id in _CAST_OVER_DISPATCH:
                # float(...)/int(...) directly over a jitted-dispatch
                # result forces the dispatch to complete now
                for sub in ast.walk(node):
                    if isinstance(sub, ast.Call) and sub is not node \
                            and index.jit_site_for(sub.func,
                                                   fi.scope):
                        msg = (f"`{fn.id}()` over a jitted dispatch "
                               f"result syncs device->host")
                        break
            if not msg:
                continue
            where = (" inside a Python loop — one pipeline stall per "
                     "iteration" if id(node) in in_loop else "")
            out.append(Violation(
                "RL002", fi.file.rel, node.lineno, node.col_offset,
                f"{msg} in plan region `{fi.qualname}`{where}; mark "
                f"deliberate token-emission syncs with "
                f"`# reprolint: sync-point`"))
    return out
