"""The plain reference computes what the program computes: full-sequence
logits of both families at float32 on seeded random weights, the
reference from its own weights and the program from its copy of them."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.lib import program
from bench.models import decoder
from bench.programs import decoder as adapter

TINY = {
    "opt": {"name": "t-opt", "registry": "opt-1.3b", "model_type": "opt",
            "hidden_size": 64, "ffn_dim": 96, "num_attention_heads": 4,
            "num_hidden_layers": 2, "vocab_size": 300,
            "max_position_embeddings": 64, "activation_function": "relu",
            "tie_word_embeddings": False, "torch_dtype": "float32"},
    "internlm2": {"name": "t-il2", "registry": "internlm2-1.8b",
                  "model_type": "internlm2", "hidden_size": 64,
                  "intermediate_size": 96, "num_attention_heads": 4,
                  "num_key_value_heads": 2, "num_hidden_layers": 2,
                  "vocab_size": 300, "max_position_embeddings": 64,
                  "hidden_act": "silu", "rms_norm_eps": 1e-6,
                  "rope_theta": 1000000, "tie_word_embeddings": False,
                  "torch_dtype": "float32"},
}
TINY["opt-tied"] = dict(TINY["opt"], tie_word_embeddings=True)


@pytest.mark.parametrize("fam", sorted(TINY))
def test_reference_matches_program_forward(fam):
    from repro.launch.mesh import make_test_mesh
    from repro.models.model import forward
    from repro.sharding import rules_for
    rules = rules_for(make_test_mesh())
    s = decoder.sizes(TINY[fam])
    cfg = adapter.arch_config(TINY[fam], s)
    key = jax.random.PRNGKey(3)
    params = program.make_params(cfg, decoder, adapter, s, key)
    tokens = jax.random.randint(jax.random.PRNGKey(4), (1, 40), 0, s["V"])
    with jax.default_matmul_precision("highest"):
        got, _ = jax.jit(lambda p, t: forward(p, cfg, rules, {"tokens": t})
                         )(params, tokens)
        ref = jax.jit(lambda t: decoder.forward(s, decoder.init(s, key), t)
                      )(tokens[0])
    got = np.asarray(got[0, :, :s["V"]])
    assert np.abs(got - np.asarray(ref)).max() < 2e-4 * max(
        1.0, np.abs(np.asarray(ref)).max())


def test_fp8_control_differs_from_reference():
    s = decoder.sizes(TINY["opt"])
    w = decoder.init(s, jax.random.PRNGKey(0))
    t = jnp.arange(20) % s["V"]
    ref = decoder.forward(s, w, t)
    low = decoder.forward(s, w, t, quant="fp8")
    rel = float(jnp.abs(ref - low).max() / jnp.abs(ref).max())
    assert 1e-3 < rel < 0.3
