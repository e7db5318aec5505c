"""The plain reference on its own: a window shorter than the padding of a
sequence leaves the real rows' logits finite."""
import numpy as np

from harness import model, spec


def test_window_shorter_than_the_padding_keeps_logits_finite():
    conf = model.rehearse_conf(spec.load_cell("qwen2_5_3b.chat_zipf64")
                               .config)
    conf.update(sliding_window=16, use_sliding_window=True)
    ref = spec.reference_module(conf)
    params = model.make_params(model.model_config(conf), conf, 1, seed=5)
    tokens = np.arange(40, dtype=np.int32)      # padded to 1024 positions
    z = ref.logits(conf, ref.reference_weights(params), {}, tokens,
                   np.arange(30, 40))
    assert z.shape == (10, conf["vocab_size"])
    assert np.isfinite(z).all()
