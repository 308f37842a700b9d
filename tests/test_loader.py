"""Checkpoint loader parity: HF safetensors → stacked pytree → our forward
must match the torch reference implementation bit-for-bit (fp32 tolerance).

No network: the tests GENERATE tiny HF-format checkpoints locally with
transformers (random weights, save_pretrained) and assert our JAX forward
and greedy decode agree with torch. This is the proof that a user pointing
the catalog at a real downloaded Llama/Mistral/Gemma/Qwen2 checkpoint gets
the real model's logits (VERDICT r1 item 1).
"""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

torch = pytest.importorskip("torch")

from quoracle_tpu.models.config import ModelConfig
from quoracle_tpu.models.generate import GenerateEngine
from quoracle_tpu.models.loader import (
    config_from_hf, load_checkpoint, register_hf_checkpoint,
)
from quoracle_tpu.models.transformer import forward, init_cache


# ---------------------------------------------------------------------------
# Checkpoint factories (tiny, random, saved in HF layout)
# ---------------------------------------------------------------------------

def _save(model, path):
    model.eval()
    model.save_pretrained(path, safe_serialization=True)
    return str(path)


def make_llama(path, **kw):
    from transformers import LlamaConfig, LlamaForCausalLM
    cfg = LlamaConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128, rope_theta=10000.0, rms_norm_eps=1e-5,
        bos_token_id=1, eos_token_id=2, attention_bias=False,
        tie_word_embeddings=False, **kw)
    torch.manual_seed(0)
    return _save(LlamaForCausalLM(cfg), path), cfg


def make_mistral(path):
    from transformers import MistralConfig, MistralForCausalLM
    cfg = MistralConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128, rope_theta=100000.0, rms_norm_eps=1e-5,
        sliding_window=8, bos_token_id=1, eos_token_id=2,
        tie_word_embeddings=False)
    torch.manual_seed(1)
    return _save(MistralForCausalLM(cfg), path), cfg


def make_gemma(path):
    from transformers import GemmaConfig, GemmaForCausalLM
    cfg = GemmaConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=4,
        head_dim=16, max_position_embeddings=128, rope_theta=10000.0,
        rms_norm_eps=1e-6, hidden_act="gelu_pytorch_tanh",
        bos_token_id=1, eos_token_id=2)   # gemma always ties embeddings
    torch.manual_seed(2)
    return _save(GemmaForCausalLM(cfg), path), cfg


def make_qwen2(path):
    from transformers import Qwen2Config, Qwen2ForCausalLM
    cfg = Qwen2Config(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128, rope_theta=10000.0, rms_norm_eps=1e-5,
        bos_token_id=1, eos_token_id=2, tie_word_embeddings=False)
    torch.manual_seed(3)
    return _save(Qwen2ForCausalLM(cfg), path), cfg


FACTORIES = {
    "llama": make_llama,
    "mistral": make_mistral,
    "gemma": make_gemma,
    "qwen2": make_qwen2,
}


def our_logits(cfg: ModelConfig, params, ids: np.ndarray) -> np.ndarray:
    B, T = ids.shape
    tokens = jnp.asarray(ids, jnp.int32)
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))
    cache = init_cache(cfg, B, T, dtype=jnp.float32)
    logits, _ = forward(params, cfg, tokens, positions, cache,
                        write_offset=jnp.zeros((B,), jnp.int32),
                        kv_lens=jnp.full((B,), T, jnp.int32))
    return np.asarray(logits)


def torch_logits(path: str, ids: np.ndarray) -> np.ndarray:
    from transformers import AutoModelForCausalLM
    model = AutoModelForCausalLM.from_pretrained(
        path, local_files_only=True, attn_implementation="eager")
    model.eval()
    with torch.no_grad():
        out = model(torch.tensor(ids, dtype=torch.long))
    return out.logits.float().numpy()


# ---------------------------------------------------------------------------
# Logit parity per family
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("family", sorted(FACTORIES))
def test_forward_matches_torch(family, tmp_path):
    path, _ = FACTORIES[family](tmp_path / family)
    cfg, params = load_checkpoint(path, name=f"{family}-parity-test",
                                  dtype=np.float32)
    params = jax.tree.map(jnp.asarray, params)

    rng = np.random.default_rng(42)
    ids = rng.integers(3, 250, (2, 16))
    ours = our_logits(cfg, params, ids)
    ref = torch_logits(path, ids)
    np.testing.assert_allclose(ours, ref, atol=2e-4, rtol=2e-4)


def test_mistral_sliding_window_parity(tmp_path):
    """T=16 > window=8 so the sliding mask actually truncates attention —
    a mask-convention mismatch would show up here, not in the short case."""
    path, _ = make_mistral(tmp_path / "m")
    cfg, params = load_checkpoint(path, name="mistral-swa-test",
                                  dtype=np.float32)
    assert cfg.sliding_window == 8
    params = jax.tree.map(jnp.asarray, params)
    ids = np.random.default_rng(7).integers(3, 250, (1, 16))
    np.testing.assert_allclose(our_logits(cfg, params, ids),
                               torch_logits(path, ids),
                               atol=2e-4, rtol=2e-4)


# ---------------------------------------------------------------------------
# Greedy decode parity through the full Engine path (cache + decode loop)
# ---------------------------------------------------------------------------

class _IdTok:
    """Identity 'tokenizer' so the engine runs on raw ids."""
    pad_id, bos_id, eos_id = 0, 1, 2

    def decode(self, ids):
        return " ".join(map(str, ids))


@pytest.mark.parametrize("family", ["llama", "gemma", "qwen2"])
def test_engine_greedy_decode_matches_torch(family, tmp_path):
    path, _ = FACTORIES[family](tmp_path / family)
    cfg, params = load_checkpoint(path, name=f"{family}-decode-test",
                                  dtype=np.float32)
    params = jax.tree.map(jnp.asarray, params)
    engine = GenerateEngine(cfg, params, _IdTok(), max_seq=64,
                            prompt_buckets=(16, 32))

    prompt = list(np.random.default_rng(9).integers(3, 250, 12))
    n_new = 8
    res = engine.generate([prompt], temperature=0.0,
                          max_new_tokens=n_new)[0]

    # torch greedy reference: step-by-step argmax over the growing sequence
    from transformers import AutoModelForCausalLM
    model = AutoModelForCausalLM.from_pretrained(
        path, local_files_only=True, attn_implementation="eager")
    model.eval()
    seq = list(prompt)
    expect = []
    with torch.no_grad():
        for _ in range(n_new):
            logits = model(torch.tensor([seq], dtype=torch.long)).logits
            nxt = int(torch.argmax(logits[0, -1]))
            expect.append(nxt)
            if nxt == cfg.eos_token_id:
                break
            seq.append(nxt)
    got = res.token_ids + ([cfg.eos_token_id]
                           if res.finish_reason == "stop" else [])
    assert got == expect, f"{family}: {got} != {expect}"


# ---------------------------------------------------------------------------
# Catalog registration + TPUBackend end-to-end on a real checkpoint
# ---------------------------------------------------------------------------

def test_register_and_backend_serves_checkpoint(tmp_path):
    path, _ = make_llama(tmp_path / "ck")
    _write_tiny_tokenizer(path)
    cfg = register_hf_checkpoint(path, name="ck-e2e-test")
    assert cfg.checkpoint_path == path

    from quoracle_tpu.models.runtime import QueryRequest, TPUBackend
    backend = TPUBackend(pool=["xla:ck-e2e-test"])
    out = backend.query([QueryRequest(
        model_spec="xla:ck-e2e-test",
        messages=[{"role": "user", "content": "hi"}],
        temperature=0.0, max_tokens=4)])
    assert len(out) == 1 and out[0].ok, out[0].error
    assert out[0].usage.prompt_tokens > 0
    backend.close()


def test_vlm_checkpoint_roundtrip_and_serves_images(tmp_path):
    """make_checkpoint --families vlm at tiny scale → loader parses
    vision_config + image_token_id, loads the tower pytree, and the
    backend serves a multimodal message through the real-checkpoint path
    (BASELINE config 5 capability)."""
    import base64
    from quoracle_tpu.models.make_checkpoint import make_checkpoint
    from quoracle_tpu.models.images import write_png
    from quoracle_tpu.models.loader import load_params
    from quoracle_tpu.models.runtime import QueryRequest, TPUBackend

    out = make_checkpoint(str(tmp_path / "vlm"), family="vlm", scale="tiny")
    cfg = register_hf_checkpoint(out, name="ck-vlm-test")
    assert cfg.vision is not None and cfg.vision.n_patches == 4
    assert cfg.image_token_id is not None

    params = load_params(out, cfg)
    vl = params["vision"]["layers"]
    assert vl["wqkv"].shape == (cfg.vision.n_layers, cfg.vision.dim,
                                3 * cfg.vision.dim)
    assert params["vision"]["projector"].shape == (cfg.vision.dim, cfg.dim)

    rng = np.random.default_rng(3)
    png = str(tmp_path / "i.png")
    write_png(png, rng.integers(0, 255, (28 * 28 * 3,),
                                dtype=np.uint8).tobytes(), 28, 28)
    b64 = base64.b64encode(open(png, "rb").read()).decode()
    backend = TPUBackend(pool=["xla:ck-vlm-test"])
    msgs = [{"role": "user", "content": [
        {"type": "text", "text": "describe"},
        {"type": "image_base64", "data": b64}]}]
    r = backend.query([QueryRequest("xla:ck-vlm-test", msgs,
                                    temperature=0.0, max_tokens=6)])[0]
    assert r.ok, r.error
    assert r.usage.prompt_tokens > cfg.vision.n_patches
    backend.close()


# ---------------------------------------------------------------------------
# Real-tokenizer path: chat template from the checkpoint directory
# ---------------------------------------------------------------------------

CHAT_TEMPLATE = (
    "{% for message in messages %}<|{{ message['role'] }}|>\n"
    "{{ message['content'] }}\n{% endfor %}"
    "{% if add_generation_prompt %}<|assistant|>\n{% endif %}")


def _write_tiny_tokenizer(path: str) -> None:
    """A real tokenizers-format BPE (char-level vocab) + chat template, in
    the checkpoint dir, exactly where HF tooling would put it."""
    from tokenizers import Tokenizer, models, pre_tokenizers, decoders
    chars = [chr(c) for c in range(32, 127)] + ["\n"]
    vocab = {"<pad>": 0, "<s>": 1, "</s>": 2}
    for ch in chars:
        vocab.setdefault(ch, len(vocab))
    tok = Tokenizer(models.BPE(vocab=vocab, merges=[], unk_token="<pad>"))
    tok.decoder = decoders.Fuse()    # char-level: join without spaces
    tok.save(os.path.join(path, "tokenizer.json"))
    with open(os.path.join(path, "tokenizer_config.json"), "w") as f:
        json.dump({
            "tokenizer_class": "PreTrainedTokenizerFast",
            "bos_token": "<s>", "eos_token": "</s>", "pad_token": "<pad>",
            "chat_template": CHAT_TEMPLATE,
        }, f)


def test_hf_auto_tokenizer_applies_chat_template(tmp_path):
    d = str(tmp_path / "tok")
    os.makedirs(d)
    _write_tiny_tokenizer(d)
    from quoracle_tpu.models.tokenizer import HFAutoTokenizer
    t = HFAutoTokenizer(d)
    assert t.bos_id == 1 and t.eos_id == 2
    ids = t.encode_chat([{"role": "user", "content": "hello"}])
    text = t.decode(ids)
    assert "hello" in text
    # template applied: the assistant generation prompt is present
    assert "<|assistant|>" in "".join(
        t._tok.convert_ids_to_tokens(ids)) or "assistant" in text


def test_config_from_hf_rejects_unknown_arch():
    with pytest.raises(ValueError):
        config_from_hf({"architectures": ["GPTBigCodeForCausalLM"],
                        "num_attention_heads": 4}, "x")


# ---------------------------------------------------------------------------
# Review-driven regressions: rope_scaling, multi-eos stops, tokenizer cache
# ---------------------------------------------------------------------------

def test_llama3_rope_scaling_parity(tmp_path):
    """Llama-3.1-style rope_scaling (llama3 scheme) must match the torch
    implementation — dropping it silently would diverge on every position."""
    path, _ = make_llama(
        tmp_path / "l31",
        rope_scaling={"rope_type": "llama3", "factor": 8.0,
                      "low_freq_factor": 1.0, "high_freq_factor": 4.0,
                      "original_max_position_embeddings": 32})
    cfg, params = load_checkpoint(path, name="llama3-rope-test",
                                  dtype=np.float32)
    assert cfg.rope_scaling == ("llama3", 8.0, 1.0, 4.0, 32)
    params = jax.tree.map(jnp.asarray, params)
    ids = np.random.default_rng(11).integers(3, 250, (1, 48))
    np.testing.assert_allclose(our_logits(cfg, params, ids),
                               torch_logits(path, ids),
                               atol=2e-4, rtol=2e-4)


def test_unsupported_rope_scaling_raises():
    with pytest.raises(ValueError, match="rope_scaling"):
        config_from_hf({"architectures": ["LlamaForCausalLM"],
                        "vocab_size": 8, "hidden_size": 8,
                        "num_hidden_layers": 1, "num_attention_heads": 2,
                        "intermediate_size": 8,
                        "rope_scaling": {"rope_type": "yarn", "factor": 2.0}},
                       "x")


def test_eos_list_maps_to_stop_token_ids():
    cfg = config_from_hf({"architectures": ["LlamaForCausalLM"],
                          "vocab_size": 8, "hidden_size": 8,
                          "num_hidden_layers": 1, "num_attention_heads": 2,
                          "intermediate_size": 8,
                          "eos_token_id": [128001, 128008, 128009]}, "x")
    assert cfg.eos_token_id == 128001
    assert cfg.stop_token_ids == (128008, 128009)
    # 0 is a legitimate id, not a missing value
    cfg0 = config_from_hf({"architectures": ["LlamaForCausalLM"],
                           "vocab_size": 8, "hidden_size": 8,
                           "num_hidden_layers": 1, "num_attention_heads": 2,
                           "intermediate_size": 8,
                           "eos_token_id": 0, "bos_token_id": 0}, "x0")
    assert cfg0.eos_token_id == 0 and cfg0.bos_token_id == 0


def test_use_sliding_window_false_disables_window():
    cfg = config_from_hf({"architectures": ["Qwen2ForCausalLM"],
                          "vocab_size": 8, "hidden_size": 8,
                          "num_hidden_layers": 1, "num_attention_heads": 2,
                          "intermediate_size": 8,
                          "sliding_window": 4096,
                          "use_sliding_window": False}, "xq")
    assert cfg.sliding_window is None


def test_decode_stops_on_secondary_stop_id(tmp_path):
    """The engine must stop on ANY id in stop_token_ids, not just eos."""
    import dataclasses
    path, _ = make_llama(tmp_path / "st")
    cfg, params = load_checkpoint(path, name="stop-ids-test",
                                  dtype=np.float32)
    params_j = jax.tree.map(jnp.asarray, params)
    engine0 = GenerateEngine(cfg, params_j, _IdTok(), max_seq=64,
                             prompt_buckets=(16,))
    prompt = list(np.random.default_rng(5).integers(3, 250, 8))
    base = engine0.generate([prompt], temperature=0.0, max_new_tokens=8)[0]
    assert len(base.token_ids) >= 2
    # declare the greedy second token a stop id → generation halts there
    second = base.token_ids[1]
    cfg2 = dataclasses.replace(cfg, name="stop-ids-test-2",
                               stop_token_ids=(second,))
    engine2 = GenerateEngine(cfg2, params_j, _IdTok(), max_seq=64,
                             prompt_buckets=(16,))
    res = engine2.generate([prompt], temperature=0.0, max_new_tokens=8)[0]
    assert res.finish_reason == "stop"
    # halts at the FIRST occurrence of the stop id (greedy may repeat
    # tokens, so the first occurrence can precede index 1)
    first_hit = base.token_ids.index(second)
    assert res.token_ids == base.token_ids[:first_hit]


def test_get_tokenizer_not_stale_after_registration(tmp_path):
    """A lookup made BEFORE registration must not pin the fallback tokenizer
    once the name is (re)registered with a real checkpoint."""
    from quoracle_tpu.models.tokenizer import HFAutoTokenizer, get_tokenizer
    name = "stale-tok-test"
    t1 = get_tokenizer(name)          # unknown name → byte/BPE fallback
    assert not isinstance(t1, HFAutoTokenizer)
    d = str(tmp_path / "ck")
    os.makedirs(d, exist_ok=True)
    path, _ = make_llama(tmp_path / "ck")
    _write_tiny_tokenizer(path)
    register_hf_checkpoint(path, name=name)
    t2 = get_tokenizer(name)
    assert isinstance(t2, HFAutoTokenizer)
