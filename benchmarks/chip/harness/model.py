"""The system under test, built from a configuration file, and its weights.

The weights are the benchmark's own: one jitted call makes the whole
parameter tree on the device from the seed, in bf16, with every LoRA bank
slot zero; one more jitted function makes adapter ``a`` from
``(seed, a)``.  The adapters reach the program through
``AdapterRegistry.load``, and the plain reference regenerates the ones it
needs after the window, so it takes nothing the program has made.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Tuple

import numpy as np

# Sizes of the CPU rehearsal: the widths of the repo's SMOKE config of the
# same architecture, and a serving shape that a CPU run can hold.
REHEARSE_SERVING = dict(num_blocks=512, max_blocks_per_slot=32,
                        num_slots=4, prefill_chunk=32, prefill_rows=2)
REHEARSE_ADAPTERS = 8


def jax_key(seed: int, stream: int):
    """A PRNG key from a seed of any size (``PRNGKey`` keeps 32 bits)."""
    import jax
    words = np.random.SeedSequence([seed, stream]).generate_state(2)
    return jax.random.wrap_key_data(words.astype(np.uint32))


def rehearse_conf(conf: Dict[str, Any]) -> Dict[str, Any]:
    """The configuration at the widths of the repo's SMOKE config of the
    same architecture (the CPU rehearsal)."""
    from repro.configs import get_smoke
    s = get_smoke(conf["repo_arch"])
    out = dict(conf, num_hidden_layers=s.num_layers, hidden_size=s.d_model,
               num_attention_heads=s.num_heads,
               num_key_value_heads=s.num_kv_heads, intermediate_size=s.d_ff,
               vocab_size=s.vocab_size,
               architecture=dict(conf["architecture"], head_dim=s.head_dim))
    if conf.get("sliding_window") and conf.get("use_sliding_window") \
            is not False:
        out["sliding_window"] = 200
    return out


def model_config(conf: Dict[str, Any]):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.models.config import LoRAConfig, ModelConfig
    arch = conf["architecture"]
    window = conf.get("sliding_window")
    if conf.get("use_sliding_window") is False:
        window = None
    lora = conf["lora"]
    cfg = ModelConfig(
        name=conf["name"], family="dense",
        num_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        num_heads=conf["num_attention_heads"],
        num_kv_heads=conf["num_key_value_heads"],
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        head_dim=arch["head_dim"], qkv_bias=arch["qkv_bias"],
        mlp_type="swiglu", norm_type="rmsnorm",
        tie_embeddings=conf["tie_word_embeddings"],
        rope_theta=float(conf["rope_theta"]), sliding_window=window,
        dtype=conf["torch_dtype"],
        lora=LoRAConfig(rank=lora["rank"], alpha=lora["alpha"],
                        targets=tuple(lora["targets"])),
        source=conf["source"])
    return cfg


def serving_shape(conf: Dict[str, Any], *, rehearse: bool = False
                  ) -> Dict[str, int]:
    sv = dict(conf["serving"])
    if rehearse:
        sv.update(REHEARSE_SERVING)
    return sv


def num_adapters(conf: Dict[str, Any], *, rehearse: bool = False) -> int:
    return REHEARSE_ADAPTERS if rehearse else conf["lora"]["adapters"]


def serving_config(sv: Dict[str, int], *, rehearse: bool = False):
    from repro.serving import ServingConfig
    # on the chip the runtime picks the Pallas SGMV kernel itself; the CPU
    # rehearsal forces it, in interpret mode
    return ServingConfig(
        num_slots=sv["num_slots"], block_size=sv["block_size"],
        num_blocks=sv["num_blocks"],
        max_blocks_per_slot=sv["max_blocks_per_slot"],
        prefill_chunk=sv["prefill_chunk"], prefill_rows=sv["prefill_rows"],
        decode_chunk=sv["decode_chunk"],
        sgmv_kernel=True if rehearse else None)


def _leaf_name(path) -> Tuple[str, ...]:
    return tuple(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)


def _param_shapes(cfg, n_adapters: int):
    """The tree structure the program expects, as shapes only."""
    import jax
    from repro.models import transformer as tf
    return jax.eval_shape(lambda k: tf.init_params(
        k, cfg, lora_adapters=n_adapters), jax.random.PRNGKey(0))


def make_params(cfg, conf: Dict[str, Any], n_adapters: int, seed: int):
    """The whole backbone (LoRA banks zero) in one jitted call."""
    import jax
    import jax.numpy as jnp
    init = conf["init"]
    shapes = _param_shapes(cfg, n_adapters)
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def build(key):
        out = []
        for i, (path, sd) in enumerate(flat):
            name = _leaf_name(path)
            k = jax.random.fold_in(key, i)
            if "lora" in name or name[-1] == "scale":
                v = (jnp.zeros if "lora" in name else jnp.ones)(
                    sd.shape, jnp.float32)
            elif name[-1] == "b":
                v = init["bias_std"] * jax.random.normal(k, sd.shape)
            elif name == ("embed",):
                v = init["embed_std"] * jax.random.normal(k, sd.shape)
            elif name == ("lm_head",):
                v = init["head_std"] * jax.random.normal(k, sd.shape)
            else:                       # a dense (.., fan_in, fan_out) weight
                v = jax.random.normal(k, sd.shape) / math.sqrt(sd.shape[-2])
            out.append(v.astype(sd.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(build)(jax_key(seed, 0))


def adapter_shapes(cfg, n_adapters: int):
    """One adapter as ``AdapterRegistry.load`` takes it: the parameter tree
    with None everywhere but the LoRA leaves, each a bank leaf without its
    adapter axis (-3)."""
    import jax
    return jax.tree_util.tree_map_with_path(
        lambda path, sd: jax.ShapeDtypeStruct(
            sd.shape[:-3] + sd.shape[-2:], sd.dtype)
        if "lora" in _leaf_name(path) else None,
        _param_shapes(cfg, n_adapters))


def make_adapter_fn(cfg, conf: Dict[str, Any], n_adapters: int):
    """A jitted ``(key) -> adapter tree``: A ~ N(0, 1/fan_in), B ~
    N(0, lora_b_std^2), in the bank's dtype."""
    import jax
    b_std = conf["init"]["lora_b_std"]
    shapes = adapter_shapes(cfg, n_adapters)
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def build(key):
        out = []
        for i, (path, sd) in enumerate(flat):
            k = jax.random.fold_in(key, i)
            z = jax.random.normal(k, sd.shape)
            scale = (1.0 / math.sqrt(sd.shape[-2])
                     if _leaf_name(path)[-1] == "a" else b_std)
            out.append((scale * z).astype(sd.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(build)


def adapter_key(seed: int, a: int):
    import jax
    return jax.random.fold_in(jax_key(seed, 1), a)


@dataclasses.dataclass
class System:
    """The runtime under test and what the harness keeps beside it."""
    cfg: Any
    runtime: Any
    registry: Any
    backbone: Any               # the benchmark-made weights, no banks
    adapter_fn: Any
    n_adapters: int
    seed: int


def build_system(conf: Dict[str, Any], seed: int, *, rehearse: bool,
                 timer) -> System:
    """Weights from the seed, the runtime, and every adapter loaded
    through the registry."""
    import jax
    from repro.serving import AdapterRegistry, ContinuousRuntime
    cfg = model_config(conf)
    n = num_adapters(conf, rehearse=rehearse)
    sv = serving_shape(conf, rehearse=rehearse)
    with timer("weights"):
        params = make_params(cfg, conf, n, seed)
        jax.block_until_ready(params)
    with timer("runtime"):
        rt = ContinuousRuntime(cfg, params, serving_config(
            sv, rehearse=rehearse))
        reg = AdapterRegistry(rt)
    with timer("adapters"):
        fn = make_adapter_fn(cfg, conf, n)
        for a in range(n):
            reg.load(adapter_name(a), fn(adapter_key(seed, a)))
        jax.block_until_ready(rt.params)
    backbone = jax.tree_util.tree_map_with_path(
        lambda path, x: None if "lora" in _leaf_name(path) else x, params)
    return System(cfg, rt, reg, backbone, fn, n, seed)


def adapter_name(a: int) -> str:
    return f"tenant{a:02d}"
