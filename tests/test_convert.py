"""Checkpoint name-compat bridge: external (PaddleNLP/HF) llama
state_dicts <-> the stacked pytree, both directions and orientations.

Reference analog: the state_dict naming contract of framework/io.py
checkpoints (SURVEY.md hard part #7)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.models import convert, llama


def _cfg():
    return llama.LlamaConfig(
        vocab_size=64, hidden_size=32, intermediate_size=48,
        num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=32, dtype=jnp.float32, use_remat=False)


def test_roundtrip_paddlenlp_names():
    cfg = _cfg()
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    sd = convert.llama_to_external_state_dict(cfg, params)
    assert "llama.layers.2.mlp.down_proj.weight" in sd
    assert sd["llama.layers.0.self_attn.q_proj.weight"].shape == (32, 32)
    back = convert.llama_from_external_state_dict(cfg, sd)
    for (n1, a1), (n2, a2) in zip(
            sorted(llama.flatten_params(params)),
            sorted(llama.flatten_params(back))):
        assert n1 == n2
        np.testing.assert_array_equal(np.asarray(a1), np.asarray(a2))


def test_hf_orientation_transposes():
    cfg = _cfg()
    params = llama.init_params(cfg, jax.random.PRNGKey(1))
    hf_sd = convert.llama_to_external_state_dict(cfg, params,
                                                 prefix="model.",
                                                 source="hf")
    # HF stores [out, in]: q_proj is square here, check the rectangular kv
    assert hf_sd["model.layers.0.self_attn.k_proj.weight"].shape == (16, 32)
    back = convert.llama_from_external_state_dict(cfg, hf_sd, source="hf")
    np.testing.assert_array_equal(np.asarray(back["layers"]["wk"]),
                                  np.asarray(params["layers"]["wk"]))
    np.testing.assert_array_equal(np.asarray(back["lm_head"]),
                                  np.asarray(params["lm_head"]))


def test_loaded_weights_run_forward():
    cfg = _cfg()
    params = llama.init_params(cfg, jax.random.PRNGKey(2))
    sd = convert.llama_to_external_state_dict(cfg, params)
    back = convert.llama_from_external_state_dict(cfg, sd)
    ids = jax.random.randint(jax.random.PRNGKey(3), (1, 6), 0, 64)
    ref, _ = llama.forward_pure(cfg, params, ids)
    got, _ = llama.forward_pure(cfg, back, ids)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(got),
                               rtol=1e-6, atol=1e-6)


def test_strict_reports_missing_and_unknown():
    cfg = _cfg()
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    sd = convert.llama_to_external_state_dict(cfg, params)
    del sd["llama.layers.1.mlp.up_proj.weight"]
    sd["llama.layers.0.rotary_emb.inv_freq"] = np.zeros(4)
    with pytest.raises(KeyError, match="missing"):
        convert.llama_from_external_state_dict(cfg, sd)
    # non-strict tolerates both
    out = convert.llama_from_external_state_dict(cfg, sd, strict=False)
    assert "w_gate" in out["layers"] and "w_up" not in out["layers"]
