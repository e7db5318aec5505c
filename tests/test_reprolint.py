"""reprolint self-tests: every rule trips on its fixture and stays
quiet on the clean twin; the allowlist markers work; the tree itself is
violation-free (the CI gate, run the same way); and seeded violations —
the runtime's retired per-item sync loop with its markers stripped, and
a real kernel index_map made to close over a mutable — are caught."""
import subprocess
import sys
from collections import Counter
from pathlib import Path

import pytest

from tools.reprolint import run_paths
from tools.reprolint.config import Config, load_config

FIXTURES = Path(__file__).parent / "fixtures" / "reprolint"
REPO = Path(__file__).parent.parent


def _run(name, **cfg_kw):
    cfg_kw.setdefault("index_paths", [])
    return run_paths([str(FIXTURES / name)], config=Config(**cfg_kw))


# --------------------------------------------------- per-rule fixtures
@pytest.mark.parametrize("rule,count,needles", [
    ("rl001", 3, ["int() on a traced value", "numpy call", ".item()"]),
    ("rl002", 3, ["inside a Python loop", "block_until_ready",
                  "over a jitted dispatch"]),
    ("rl003", 2, ["read after being donated", "inside a loop without "
                  "rebinding"]),
    ("rl004", 5, ["mutable/stateful value", "grid rank 2",
                  "without masking -1", "VMEM working set",
                  "lane dim 200"]),
    ("rl005", 3, ['dtype="float64"', "astype(float)", "float64"]),
])
def test_rule_trips_on_fixture(rule, count, needles):
    vs = _run(f"{rule}_trip.py")
    rid = rule.upper()
    assert Counter(v.rule for v in vs) == {rid: count}, \
        [v.render() for v in vs]
    blob = "\n".join(v.message for v in vs)
    for needle in needles:
        assert needle in blob, (needle, blob)


@pytest.mark.parametrize(
    "rule", ["rl001", "rl002", "rl003", "rl004", "rl005"])
def test_rule_quiet_on_clean_fixture(rule):
    vs = _run(f"{rule}_clean.py")
    assert vs == [], [v.render() for v in vs]


def test_sync_point_marker_allowlists_rl002(tmp_path):
    """rl002_clean minus its marker must trip — proving the clean run
    above passes BECAUSE of the allowlist, not because the pattern is
    invisible."""
    text = (FIXTURES / "rl002_clean.py").read_text()
    assert "# reprolint: sync-point" in text
    p = tmp_path / "unmarked.py"
    p.write_text(text.replace("# reprolint: sync-point", "#"))
    vs = run_paths([str(p)], config=Config(index_paths=[]))
    assert [v.rule for v in vs] == ["RL002"]


def test_disable_marker(tmp_path):
    text = (FIXTURES / "rl005_trip.py").read_text()
    p = tmp_path / "suppressed.py"
    p.write_text(text.replace("# trips", "# reprolint: disable=RL005"))
    assert run_paths([str(p)], config=Config(index_paths=[])) == []


def test_rule_selection_config():
    vs = _run("rl005_trip.py", disable=["RL005"])
    assert vs == []
    vs = _run("rl001_trip.py", enable=["RL002"])
    assert vs == []


# ------------------------------------------------------ the tree gate
def test_tree_is_clean():
    """Mirror of CI's `analysis` job: the shipped tree must be
    violation-free under the pyproject config."""
    proc = subprocess.run(
        [sys.executable, "-m", "tools.reprolint", "src/", "benchmarks/"],
        cwd=REPO, capture_output=True, text=True)
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_pyproject_config_loads():
    cfg = load_config(REPO)
    assert cfg.vmem_budget_mib == 16.0
    assert any("try_admit" in p for p in cfg.plan_functions)
    assert "tests/fixtures/reprolint" in cfg.exclude


# --------------------------------------------------- seeded violations
def test_seeded_runtime_sync_loop_caught(tmp_path):
    """Strip the two deliberate sync-point markers from the real
    runtime: the token-emission syncs (the shape of the retired
    per-item admission loop) must surface as RL002 — i.e. the shipped
    tree is clean because the syncs are *annotated*, not unseen."""
    text = (REPO / "src/repro/serving/runtime.py").read_text()
    assert text.count("# reprolint: sync-point") == 2
    p = tmp_path / "runtime.py"
    p.write_text(text.replace("# reprolint: sync-point", "#"))
    vs = run_paths([str(p)], config=Config(index_paths=["src"]))
    rl002 = [v for v in vs if v.rule == "RL002"]
    assert len(rl002) >= 2, [v.render() for v in vs]
    assert any("numpy.asarray" in v.message for v in rl002)


def test_seeded_index_map_mutable_closure_caught(tmp_path):
    """Make the real paged-attention index_map close over a mutable
    module-level list: RL004 must flag it (and the unmodified copy must
    stay clean, so the flag is the seed, not noise)."""
    src = REPO / "src/repro/kernels/paged_attention/paged_attn.py"
    text = src.read_text()
    old = "    def row_map(b, tbl, pos):"
    assert old in text
    seeded = '_SCHEDULE = [0]\n' + text.replace(
        old, old + "\n        _ = _SCHEDULE[0]")
    clean_copy = tmp_path / "paged_attn_clean.py"
    clean_copy.write_text(text)
    assert run_paths([str(clean_copy)],
                     config=Config(index_paths=[])) == []
    p = tmp_path / "paged_attn_seeded.py"
    p.write_text(seeded)
    vs = run_paths([str(p)], config=Config(index_paths=[]))
    assert any(v.rule == "RL004" and "_SCHEDULE" in v.message
               for v in vs), [v.render() for v in vs]
