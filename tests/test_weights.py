"""Weight loading: HF safetensors -> stacked params, verified against the HF
(torch CPU) forward pass on locally generated tiny checkpoints — the
zero-egress analogue of "bench runs TinyLlama with real weights and matches
HF logits" (no downloads possible in CI; architecture coverage is identical).
"""

import json

import numpy as np
import pytest

jnp = pytest.importorskip("jax.numpy")
torch = pytest.importorskip("torch")

import jax

from kubernetes_gpu_cluster_tpu.engine.weights import (
    config_from_hf, load_weights, resolve_model)
from kubernetes_gpu_cluster_tpu.models import llama as model_lib
from kubernetes_gpu_cluster_tpu.models.registry import resolve


def _hf_llama_dir(tmp_path, tie=False, qwen2=False):
    from transformers import LlamaConfig, LlamaForCausalLM
    from transformers import Qwen2Config, Qwen2ForCausalLM

    kw = dict(vocab_size=128, hidden_size=64, intermediate_size=128,
              num_hidden_layers=2, num_attention_heads=4,
              num_key_value_heads=2, max_position_embeddings=256,
              rope_theta=10000.0, rms_norm_eps=1e-5,
              tie_word_embeddings=tie)
    torch.manual_seed(0)
    if qwen2:
        model = Qwen2ForCausalLM(Qwen2Config(**kw))
    else:
        model = LlamaForCausalLM(LlamaConfig(**kw, attention_bias=False))
    model.eval()
    d = tmp_path / ("qwen2" if qwen2 else f"llama{'-tied' if tie else ''}")
    model.save_pretrained(d, safe_serialization=True)
    return model, str(d)


def _our_logits(path, prompt):
    cfg = config_from_hf(path).replace(dtype="float32")
    params = load_weights(path, cfg)
    T = len(prompt)
    meta = model_lib.StepMeta(
        seg_ids=jnp.zeros((T,), jnp.int32),
        positions=jnp.arange(T, dtype=jnp.int32),
        slot_mapping=jnp.arange(T, dtype=jnp.int32),  # scratch pool below
        logits_indices=jnp.asarray([T - 1], jnp.int32))
    from kubernetes_gpu_cluster_tpu.config import CacheConfig
    from kubernetes_gpu_cluster_tpu.engine.kv_cache import allocate_kv_cache
    kv = allocate_kv_cache(cfg, CacheConfig(page_size=16, num_pages=4), 4)
    _, _, h = model_lib.forward(params, cfg, jnp.asarray(prompt), meta, kv)
    h = model_lib._norm(cfg, h, params, "final_norm")
    return np.asarray(model_lib.compute_logits(params, cfg, h))   # [T, V]


class TestHFParity:
    @pytest.mark.parametrize("tie", [False, True])
    def test_llama_logits_match(self, tmp_path, tie):
        model, path = _hf_llama_dir(tmp_path, tie=tie)
        prompt = [1, 17, 99, 4, 63, 2, 118, 30]
        with torch.no_grad():
            ref = model(torch.tensor([prompt])).logits[0].numpy()
        got = _our_logits(path, prompt)
        np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)

    def test_qwen2_logits_match(self, tmp_path):
        model, path = _hf_llama_dir(tmp_path, qwen2=True)
        prompt = [3, 8, 110, 5]
        with torch.no_grad():
            ref = model(torch.tensor([prompt])).logits[0].numpy()
        got = _our_logits(path, prompt)
        np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)


def _hf_opt_dir(tmp_path):
    from transformers import OPTConfig, OPTForCausalLM
    cfg = OPTConfig(
        vocab_size=128, hidden_size=64, ffn_dim=128, num_hidden_layers=2,
        num_attention_heads=4, max_position_embeddings=256,
        do_layer_norm_before=True, activation_function="relu")
    torch.manual_seed(3)
    model = OPTForCausalLM(cfg).eval()
    d = tmp_path / "opt"
    model.save_pretrained(d, safe_serialization=True)
    return model, str(d)


class TestOPTParity:
    """The reference's minimal-example model family (facebook/opt-125m,
    reference values-01-minimal-example.yaml:4-8), served through the shared
    decoder graph via config flags (learned positions, pre-LN LayerNorm,
    biased ReLU MLP, tied head)."""

    def test_opt_logits_match_hf(self, tmp_path):
        model, path = _hf_opt_dir(tmp_path)
        prompt = [2, 17, 99, 4, 63, 30]
        with torch.no_grad():
            ref = model(torch.tensor([prompt])).logits[0].numpy()
        got = _our_logits(path, prompt)
        np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)

    def test_opt_config_fields(self, tmp_path):
        _, path = _hf_opt_dir(tmp_path)
        cfg = config_from_hf(path)
        assert cfg.norm_type == "layernorm"
        assert cfg.pos_embedding == "learned"
        assert cfg.mlp_type == "mlp" and cfg.mlp_act == "relu"
        assert cfg.linear_bias and cfg.attention_bias
        assert cfg.tie_word_embeddings
        assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (4, 4, 16)

    def test_opt_engine_greedy_matches_hf(self, tmp_path):
        model, path = _hf_opt_dir(tmp_path)
        from kubernetes_gpu_cluster_tpu.config import (
            CacheConfig, EngineConfig, SchedulerConfig)
        from kubernetes_gpu_cluster_tpu.engine import LLMEngine, SamplingParams

        cfg = config_from_hf(path).replace(dtype="float32")
        params = load_weights(path, cfg)
        eng = LLMEngine(
            EngineConfig(model=cfg,
                         cache=CacheConfig(page_size=16, num_pages=64),
                         scheduler=SchedulerConfig(
                             max_num_seqs=2, max_prefill_tokens=64,
                             decode_buckets=(1, 2), prefill_buckets=(32, 64),
                             decode_window=2)),
            params=params)
        prompt = [2, 5, 9, 33]
        out = eng.generate([prompt], SamplingParams(max_tokens=6,
                                                    temperature=0.0))[0]
        with torch.no_grad():
            ids = torch.tensor([prompt])
            hf_tokens = []
            for _ in range(6):
                nxt = model(ids).logits[0, -1].argmax().item()
                hf_tokens.append(nxt)
                ids = torch.cat([ids, torch.tensor([[nxt]])], dim=1)
        assert out.output_token_ids == hf_tokens

    def test_opt_preset_resolves(self):
        from kubernetes_gpu_cluster_tpu.config import get_model_config
        cfg = get_model_config("facebook/opt-125m")
        assert cfg.name == "opt-125m" and cfg.pos_embedding == "learned"

    def test_opt_tp_sharded_load_matches(self, tmp_path):
        """OPT under a tp=2 mesh: sharded placement + GSPMD serving parity."""
        import jax
        from kubernetes_gpu_cluster_tpu.engine.engine import resolve_shardings
        from kubernetes_gpu_cluster_tpu.parallel import make_mesh

        model, path = _hf_opt_dir(tmp_path)
        cfg = config_from_hf(path).replace(dtype="float32")
        full = load_weights(path, cfg)
        mesh = make_mesh(tp=2)
        shardings, _ = resolve_shardings(mesh, cfg)
        sharded = load_weights(path, cfg, shardings=shardings)
        for a, b in zip(jax.tree.leaves(full), jax.tree.leaves(sharded)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestConfigFromHF:
    def test_fields(self, tmp_path):
        _, path = _hf_llama_dir(tmp_path)
        cfg = config_from_hf(path)
        assert (cfg.vocab_size, cfg.hidden_size, cfg.num_layers) == (128, 64, 2)
        assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (4, 2, 16)
        assert not cfg.attention_bias and not cfg.qk_norm and not cfg.is_moe

    def test_resolve_local_dir_vs_preset(self, tmp_path):
        _, path = _hf_llama_dir(tmp_path)
        cfg, weights, tok = resolve_model(path)
        assert weights == path and tok == path
        r = resolve("tinyllama-1.1b")
        assert r.weights_path is None and r.config.name == "tinyllama-1.1b"


class TestEngineWithRealWeights:
    def test_generate_with_loaded_weights(self, tmp_path):
        """End-to-end: engine serves a loaded checkpoint, greedy tokens match
        HF greedy continuation."""
        model, path = _hf_llama_dir(tmp_path)
        from kubernetes_gpu_cluster_tpu.config import (
            CacheConfig, EngineConfig, SchedulerConfig)
        from kubernetes_gpu_cluster_tpu.engine import LLMEngine, SamplingParams

        cfg = config_from_hf(path).replace(dtype="float32")
        params = load_weights(path, cfg)
        eng = LLMEngine(
            EngineConfig(model=cfg,
                         cache=CacheConfig(page_size=16, num_pages=64),
                         scheduler=SchedulerConfig(
                             max_num_seqs=2, max_prefill_tokens=64,
                             decode_buckets=(1, 2), prefill_buckets=(32, 64),
                             decode_window=2)),
            params=params)
        prompt = [1, 5, 9, 33]
        out = eng.generate([prompt], SamplingParams(max_tokens=6,
                                                    temperature=0.0))[0]
        with torch.no_grad():
            ids = torch.tensor([prompt])
            hf_tokens = []
            for _ in range(6):
                nxt = model(ids).logits[0, -1].argmax().item()
                hf_tokens.append(nxt)
                ids = torch.cat([ids, torch.tensor([[nxt]])], dim=1)
        assert out.output_token_ids == hf_tokens


class TestRopeScaling:
    def test_llama3_rope_scaling_parsed_and_applied(self, tmp_path):
        import json
        import numpy as np
        from kubernetes_gpu_cluster_tpu.engine.weights import config_from_hf
        from kubernetes_gpu_cluster_tpu.ops.rope import scaled_inv_freq
        hf = {"architectures": ["LlamaForCausalLM"], "vocab_size": 128,
              "hidden_size": 64, "intermediate_size": 128,
              "num_hidden_layers": 2, "num_attention_heads": 4,
              "num_key_value_heads": 2, "rope_theta": 500000.0,
              "rope_scaling": {"rope_type": "llama3", "factor": 8.0,
                               "low_freq_factor": 1.0, "high_freq_factor": 4.0,
                               "original_max_position_embeddings": 8192}}
        (tmp_path / "config.json").write_text(json.dumps(hf))
        cfg = config_from_hf(str(tmp_path))
        scaling = cfg.rope_scaling_dict
        assert scaling["rope_type"] == "llama3"
        scaled = scaled_inv_freq(cfg.head_dim, cfg.rope_theta, scaling)
        plain = scaled_inv_freq(cfg.head_dim, cfg.rope_theta, None)
        # high-frequency components untouched; lowest stretched by ~factor
        assert np.isclose(scaled[0], plain[0])
        assert np.isclose(scaled[-1], plain[-1] / 8.0, rtol=0.2)

    def test_unsupported_rope_scaling_rejected(self, tmp_path):
        import json
        import pytest
        from kubernetes_gpu_cluster_tpu.engine.weights import config_from_hf
        hf = {"architectures": ["LlamaForCausalLM"], "vocab_size": 128,
              "hidden_size": 64, "intermediate_size": 128,
              "num_hidden_layers": 2, "num_attention_heads": 4,
              "rope_scaling": {"rope_type": "yarn", "factor": 4.0}}
        (tmp_path / "config.json").write_text(json.dumps(hf))
        with pytest.raises(ValueError, match="yarn"):
            config_from_hf(str(tmp_path))
