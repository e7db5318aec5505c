# OPTIONAL layer. Add <name>.py (or .cu) + ops.py + ref.py ONLY
# for compute hot-spots the paper itself optimizes with a custom
# kernel. Leave this package empty if the paper has none.


def largest_divisor_block(n: int, target: int) -> int:
    """Largest block size <= ``target`` that divides ``n`` exactly — the
    shared tail-safe tiling rule (min(target, n) alone crashes on
    non-divisible lengths like n=768 with target=512)."""
    b = max(min(target, n), 1)
    while n % b:
        b -= 1
    return b


# Kernel registry: name -> (ops module, public entry point).  Every kernel
# ships <name>.py (Pallas), ref.py (pure-jnp oracle), ops.py (layout
# adaptation + backend dispatch); callers resolve through here so serving /
# benchmark code never hard-codes module paths.
KERNEL_REGISTRY = {
    "flash_attention": ("repro.kernels.flash_attention.ops", "flash_mha"),
    "decode_attention": ("repro.kernels.decode_attention.ops", "decode_gqa"),
    "paged_attention": ("repro.kernels.paged_attention.ops",
                        "paged_decode_gqa"),
    "paged_prefill": ("repro.kernels.paged_prefill.ops",
                      "paged_prefill_gqa"),
    "sgmv": ("repro.kernels.sgmv.ops", "sgmv_apply"),
}


def get_kernel(name: str):
    """Resolve a registered kernel's dispatch entry point (lazy import)."""
    import importlib
    if name not in KERNEL_REGISTRY:
        raise KeyError(
            f"unknown kernel {name!r}; registered: "
            f"{sorted(KERNEL_REGISTRY)}")
    mod, fn = KERNEL_REGISTRY[name]
    return getattr(importlib.import_module(mod), fn)
