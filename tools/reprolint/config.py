"""Configuration for reprolint (``[tool.reprolint]`` in pyproject.toml).

Keys (all optional; dashes or underscores both accepted):

* ``enable`` / ``disable`` — lists of rule IDs (default: all enabled).
* ``exclude`` — path substrings/globs never analyzed or indexed.
* ``index-paths`` — extra roots always added to the project index so
  cross-module call resolution works when linting a subset (default
  ``["src"]``).
* ``plan-functions`` — RL002 scope: ``fnmatch`` patterns over
  ``module:Qual.name`` naming the scheduling functions that sit between
  device dispatches (the "plan region").
* ``vmem-budget-mib`` — RL004 per-kernel VMEM budget (default 16).

Python 3.10 has no ``tomllib``, and this tool must not grow deps, so a
minimal line-oriented TOML-section reader backs it up (flat keys with
string/int/float/bool/list-of-string values only — exactly what the
``[tool.reprolint]`` section uses).
"""
from __future__ import annotations

import dataclasses
import fnmatch
import re
from pathlib import Path
from typing import Dict, List, Optional

ALL_RULES = ("RL001", "RL002", "RL003", "RL004", "RL005")

DEFAULT_PLAN_FUNCTIONS = (
    "*:ContinuousRuntime.try_admit",
    "*:ContinuousRuntime._admit",
    "*:ContinuousRuntime._plan_blocks",
    "*:ContinuousRuntime._chunk_prefill",
    "*:ContinuousRuntime._ensure_blocks",
    "*:ContinuousRuntime._reclaim_window",
    "*:ContinuousRuntime.decode",
    "*:ContinuousRuntime._emit",
)


@dataclasses.dataclass
class Config:
    enable: List[str] = dataclasses.field(
        default_factory=lambda: list(ALL_RULES))
    disable: List[str] = dataclasses.field(default_factory=list)
    exclude: List[str] = dataclasses.field(default_factory=list)
    index_paths: List[str] = dataclasses.field(
        default_factory=lambda: ["src"])
    plan_functions: List[str] = dataclasses.field(
        default_factory=lambda: list(DEFAULT_PLAN_FUNCTIONS))
    vmem_budget_mib: float = 16.0

    def rule_enabled(self, rule_id: str) -> bool:
        return rule_id in self.enable and rule_id not in self.disable

    def is_plan_function(self, qualified: str) -> bool:
        """``qualified`` is ``module:Cls.meth`` (module may be '')."""
        return any(
            fnmatch.fnmatch(qualified, pat) for pat in self.plan_functions)


def _parse_toml_section(text: str, section: str) -> Dict[str, object]:
    """Tiny fallback parser: flat keys inside one ``[section]``."""
    out: Dict[str, object] = {}
    in_section = False
    pending = ""
    pending_key = ""
    for raw in text.splitlines():
        line = raw.strip()
        if pending_key:
            pending += " " + line
            if line.endswith("]"):
                out[pending_key] = _parse_toml_value(pending.strip())
                pending_key = pending = ""
            continue
        if line.startswith("["):
            in_section = line == f"[{section}]"
            continue
        if not in_section or not line or line.startswith("#"):
            continue
        m = re.match(r"([A-Za-z0-9_-]+)\s*=\s*(.*)$", line)
        if not m:
            continue
        key, val = m.group(1), m.group(2).strip()
        if val.startswith("[") and not val.endswith("]"):
            pending_key, pending = key, val  # multi-line list
            continue
        out[key] = _parse_toml_value(val)
    return out


def _parse_toml_value(val: str) -> object:
    val = val.strip()
    if val.startswith("[") and val.endswith("]"):
        items = []
        for part in re.findall(r'"((?:[^"\\]|\\.)*)"', val[1:-1]):
            items.append(part)
        return items
    if val.startswith('"') and val.endswith('"'):
        return val[1:-1]
    if val in ("true", "false"):
        return val == "true"
    try:
        return int(val)
    except ValueError:
        pass
    try:
        return float(val)
    except ValueError:
        return val


def _read_tool_table(pyproject: Path) -> Dict[str, object]:
    text = pyproject.read_text()
    try:
        import tomllib  # py311+

        data = tomllib.loads(text)
        table = data.get("tool", {}).get("reprolint", {})
        return dict(table)
    except ModuleNotFoundError:
        return _parse_toml_section(text, "tool.reprolint")


def load_config(root: Optional[Path] = None) -> Config:
    root = root or Path.cwd()
    cfg = Config()
    pyproject = root / "pyproject.toml"
    if not pyproject.is_file():
        return cfg
    table = _read_tool_table(pyproject)
    for key, value in table.items():
        attr = key.replace("-", "_")
        if attr == "enable" and isinstance(value, list):
            cfg.enable = [str(v) for v in value]
        elif attr == "disable" and isinstance(value, list):
            cfg.disable = [str(v) for v in value]
        elif attr == "exclude" and isinstance(value, list):
            cfg.exclude = [str(v) for v in value]
        elif attr == "index_paths" and isinstance(value, list):
            cfg.index_paths = [str(v) for v in value]
        elif attr == "plan_functions" and isinstance(value, list):
            cfg.plan_functions = [str(v) for v in value]
        elif attr == "vmem_budget_mib":
            cfg.vmem_budget_mib = float(value)  # type: ignore[arg-type]
    return cfg
