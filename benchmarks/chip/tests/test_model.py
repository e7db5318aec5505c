"""How the harness builds the program from a configuration file: the
published keys mapped to the program's fields, the block
``architecture.program`` passed through as is, and the published norm eps
reaching the program or the configuration refused."""
import dataclasses
import json
from pathlib import Path

from harness import model
from repro.models import config as program_config
from repro.models.config import LoRAConfig, ModelConfig

HERE = Path(__file__).parent
QWEN = json.loads((HERE.parent / "configs" / "qwen2_5_3b.json").read_text())
PHI = json.loads((HERE / "fixtures" / "phi3_medium_shape.json").read_text())


def test_the_cell_builds_the_program_it_always_ran():
    cfg = model.model_config(QWEN)
    want = ModelConfig(
        name="qwen2_5_3b", family="dense", num_layers=36, d_model=2048,
        num_heads=16, num_kv_heads=2, d_ff=11008, vocab_size=151936,
        head_dim=128, qkv_bias=True, mlp_type="swiglu", norm_type="rmsnorm",
        tie_embeddings=True, rope_theta=1e6, sliding_window=None,
        dtype="bfloat16", lora=LoRAConfig(rank=16, alpha=32.0,
                                          targets=("q", "k", "v", "o")),
        source=QWEN["source"])
    for f in dataclasses.fields(want):
        assert getattr(cfg, f.name) == getattr(want, f.name), f.name
    assert getattr(cfg, "norm_eps", 1e-6) == 1e-6


def test_program_block_and_eps_reach_the_program(monkeypatch):
    # a ModelConfig that takes the eps, and a program block off the
    # defaults: each value the file gives is the value the program gets
    @dataclasses.dataclass(frozen=True)
    class WithEps(program_config.ModelConfig):
        norm_eps: float = 1e-6

    monkeypatch.setattr(program_config, "ModelConfig", WithEps)
    conf = dict(PHI, architecture=dict(PHI["architecture"], program={
        "family": "dense", "mlp_type": "gelu", "norm_type": "layernorm"}))
    cfg = model.model_config(conf)
    assert cfg.norm_eps == 1e-5
    assert (cfg.family, cfg.mlp_type, cfg.norm_type) == ("dense", "gelu",
                                                         "layernorm")
    assert not cfg.tie_embeddings and cfg.sliding_window == 2047
    assert (cfg.num_layers, cfg.num_heads, cfg.num_kv_heads) == (10, 40, 10)


def test_published_eps_reaches_the_program_or_is_refused():
    # never a program at one eps checked against the reference at another
    try:
        cfg = model.model_config(PHI)
    except ValueError as e:
        assert "rms_norm_eps 1e-05" in str(e)
    else:
        assert cfg.norm_eps == 1e-5


def test_rehearsal_shrinks_widths_and_keeps_the_program_block():
    small = model.rehearse_conf(PHI)
    assert small["architecture"]["program"] == PHI["architecture"]["program"]
    assert small["hidden_size"] < PHI["hidden_size"]
    assert small["rms_norm_eps"] == PHI["rms_norm_eps"]
    assert small["tie_word_embeddings"] is False
