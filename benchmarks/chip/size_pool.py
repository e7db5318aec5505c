"""Size a configuration's KV pool without a chip.

    python3 benchmarks/chip/size_pool.py --config qwen2_5_3b \
        --blocks 4096 6144 8192

Compiles the served decode and prefill programs for a described TPU v5e
(no chip attached; ``JAX_PLATFORMS=cpu``) at the configuration's serving
shape with each pool size, and prints what ``memory_analysis()`` says each
program needs: arguments (weights, the 64-adapter LoRA bank, the pool) and
temporaries.  The largest pool whose programs fit one chip's HBM with
headroom is the ``num_blocks`` the configuration file states.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(HERE.parents[1] / "src"))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--blocks", type=int, nargs="+", required=True)
    args = ap.parse_args()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    jax.config.update("jax_enable_compilation_cache", False)
    from harness import model
    import repro.kernels.paged_attention.ops as pa
    import repro.kernels.paged_prefill.ops as pp
    import repro.kernels.sgmv.ops as sg
    from repro.models import transformer as tf
    from repro.models.cache import init_paged_cache
    for mod in (pa, pp, sg):                  # compile the Pallas paths
        mod._on_tpu = lambda: True

    with open(HERE / "configs" / f"{args.config}.json") as f:
        conf = json.load(f)
    cfg = model.model_config(conf)
    n = model.num_adapters(conf)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one),
            tree)

    params = on_chip(jax.eval_shape(lambda k: tf.init_params(
        k, cfg, lora_adapters=n), jax.random.PRNGKey(0)))
    for nb in args.blocks:
        sv = dict(model.serving_shape(conf), num_blocks=nb)
        cache = on_chip(jax.eval_shape(lambda: init_paged_cache(
            cfg, nb, sv["block_size"])))
        rt = _runtime_shapes(cfg, sv, params, cache)
        for kind in ("decode", "prefill"):
            args_ = on_chip(jax.eval_shape(lambda: rt._warmup_args(kind)))
            args_ = (params,) + tuple(args_[1:])
            fn = rt._decode if kind == "decode" else rt._prefill
            ma = fn.lower(*args_).compile().memory_analysis()
            gib = 2 ** 30
            print(json.dumps({
                "config": args.config, "num_blocks": nb, "program": kind,
                "argument_gib": ma.argument_size_in_bytes / gib,
                "temp_gib": ma.temp_size_in_bytes / gib,
                "output_gib": ma.output_size_in_bytes / gib,
                "alias_gib": ma.alias_size_in_bytes / gib,
                "total_gib": (ma.argument_size_in_bytes
                              + ma.temp_size_in_bytes
                              + ma.output_size_in_bytes
                              - ma.alias_size_in_bytes) / gib}),
                flush=True)
    return 0


def _runtime_shapes(cfg, sv, params, cache):
    """A ContinuousRuntime whose pool and weights are shapes: built on a
    two-block pool, then handed the described pool."""
    from harness import model
    from repro.serving import ContinuousRuntime
    rt = ContinuousRuntime(cfg, params, model.serving_config(
        dict(sv, num_blocks=2)))
    rt.cache = cache
    rt.scfg = model.serving_config(sv)
    return rt


if __name__ == "__main__":
    sys.exit(main())
