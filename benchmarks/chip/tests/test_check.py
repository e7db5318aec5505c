"""The comparison that decides ``correct``, at the CPU rehearsal's size.

The program passes it; the fp8 control put in the program's place opens a
gap several times the program's (at these widths its gap stays under the
cell's limit: the control is judged against the limit on the chip, at the
cell's size, by ``calibrate.py --control``); and a run whose timed path is
broken underneath (the decode step returning its KV state unchanged, or a
served token altered where it is produced) reads ``correct`` false by the
run's own rule and the cell's own limit (``check.verdict``).  Each run
here is the harness's own run minus its look for a chip: set-up, requests
in service, pre-roll, window, drain, check.
"""
import functools

import jax
import jax.numpy as jnp
import pytest

import run_cell
from harness import check, model, spec

CELL = "qwen2_5_3b.chat_zipf64"


def small_cell(name=CELL):
    cell = spec.load_cell(name)
    cell.config = model.rehearse_conf(cell.config)
    # a short pre-roll at a rate that keeps the small slots busy
    cell.traffic = dict(cell.traffic, preroll_s=3.0,
                        arrivals=dict(cell.traffic["arrivals"],
                                      rate_per_s=1.0))
    return cell


def serve(cell, seed, seconds=5.0, fault=None):
    """One rehearsal run; ``fault`` wraps the runtime's decode program."""
    timer = run_cell.Timer()
    system, tr, warm, _ = run_cell.setup(cell, seed, seconds, True, timer)
    rt = system.runtime
    if fault is not None:
        inner = rt._decode
        broken = functools.partial(fault, inner)
        broken._cache_size = inner._cache_size
        rt._decode = broken
    lg, _, _, err = run_cell.run_window(system, tr, warm, seconds, False,
                                        "", timer, rehearse=True)
    # a fault's own eager ops may compile inside the window; the sound
    # path must not
    assert fault is not None or err is None
    ref = spec.reference_module(cell.config)
    return check.gaps(cell.config, ref,
                      check.reference_weights(system.backbone),
                      lambda a: system.adapter_fn(model.adapter_key(seed,
                                                                    a)),
                      check.sample(lg.recs, seed,
                                   min_tokens=check.min_tokens(True)),
                      control=True)


@pytest.mark.parametrize("seed", [2**31 + 5, 17, 90210])
def test_control_fails_where_the_program_passes(seed):
    g = serve(small_cell(), seed)
    assert g.tokens >= check.min_tokens(True)
    # the fp8 control's widest gap stands well clear of the program's
    assert g.control > 3 * g.program
    assert g.control > 0.02


def _state_unchanged(inner, params, tok, cache, *rest):
    keep = jax.tree_util.tree_map(jnp.copy, cache)
    toks, _ = inner(params, tok, cache, *rest)
    return toks, keep


def _token_altered(inner, params, tok, cache, *rest):
    toks, cache = inner(params, tok, cache, *rest)
    return toks.at[:, -1].set((toks[:, -1] + 1) % 512), cache


@pytest.mark.parametrize("fault", [_state_unchanged, _token_altered],
                         ids=["state_unchanged", "token_altered"])
def test_broken_timed_path_reads_incorrect(fault):
    seed = 4242
    cell = small_cell()
    least = check.min_tokens(True)
    sound = serve(cell, seed)
    broken = serve(cell, seed, fault=fault)
    assert check.verdict(cell.config, sound.program, sound.tokens,
                         min_tokens=least)[0]
    assert broken.tokens >= least
    assert not check.verdict(cell.config, broken.program, broken.tokens,
                             min_tokens=least)[0]
