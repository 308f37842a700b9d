"""VLM member (BASELINE config 5): in-tree ViT tower → projected patches
splice into the decoder as soft tokens, end to end through the engine and
the TPU backend's multimodal message path.
"""

import base64
import json

import jax
import jax.numpy as jnp
import numpy as np

from quoracle_tpu.models.config import get_model_config
from quoracle_tpu.models.generate import GenerateEngine
from quoracle_tpu.models.images import write_png
from quoracle_tpu.models.runtime import QueryRequest, TPUBackend
from quoracle_tpu.models.tokenizer import ByteTokenizer
from quoracle_tpu.models.transformer import init_params
from quoracle_tpu.models.vision import (
    VisionConfig, init_vision_params, splice_image_embeds, vision_encode,
)


def make_vlm_engine():
    cfg = get_model_config("xla:tiny-vlm")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    return GenerateEngine(cfg, params, ByteTokenizer(), max_seq=256,
                          prompt_buckets=(32, 64, 128))


def img(seed: float) -> np.ndarray:
    vc = get_model_config("xla:tiny-vlm").vision
    x = np.linspace(-1, 1, vc.image_size, dtype=np.float32)
    grid = np.stack(np.meshgrid(x, x), -1).sum(-1)
    return np.stack([np.sin(grid * 3 + seed), np.cos(grid * 2 - seed),
                     grid * 0 + np.tanh(seed)], axis=-1)


def vlm_prompt(tok, cfg, text="describe the image: "):
    return (tok.encode(text, add_bos=True)
            + [cfg.image_token_id] * cfg.vision.n_patches
            + tok.encode(" answer:"))


# ---------------------------------------------------------------------------
# Tower units
# ---------------------------------------------------------------------------

def test_vision_encode_shapes_and_determinism():
    vc = VisionConfig(image_size=28, patch_size=14, dim=32, n_layers=2,
                      n_heads=2, ffn_dim=64, out_dim=48)
    params = init_vision_params(vc, jax.random.PRNGKey(1), dtype=jnp.float32)
    pixels = jnp.asarray(np.stack([img(0.1)[:, :, :], img(0.9)]))
    out = vision_encode(params, vc, pixels)
    assert out.shape == (2, vc.n_patches, 48)
    out2 = vision_encode(params, vc, pixels)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))
    # different images produce different patch embeddings
    assert not np.allclose(np.asarray(out[0]), np.asarray(out[1]))


def test_splice_replaces_only_placeholders():
    B, T, D, P = 1, 6, 4, 3
    embeds = jnp.zeros((B, T, D))
    tokens = jnp.asarray([[7, 3, 3, 3, 9, 9]], jnp.int32)   # placeholders=3
    patches = jnp.arange(B * P * D, dtype=jnp.float32).reshape(B, P, D) + 1
    out = np.asarray(splice_image_embeds(embeds, tokens, patches, 3))
    np.testing.assert_array_equal(out[0, 0], np.zeros(D))       # text kept
    np.testing.assert_array_equal(out[0, 1], np.asarray(patches[0, 0]))
    np.testing.assert_array_equal(out[0, 3], np.asarray(patches[0, 2]))
    np.testing.assert_array_equal(out[0, 4], np.zeros(D))


# ---------------------------------------------------------------------------
# Engine path
# ---------------------------------------------------------------------------

def test_engine_generates_conditioned_on_image():
    eng = make_vlm_engine()
    cfg = eng.cfg
    prompt = vlm_prompt(eng.tokenizer, cfg)
    a = eng.generate([prompt], temperature=0.0, max_new_tokens=12,
                     images=[img(0.2)])[0]
    b = eng.generate([prompt], temperature=0.0, max_new_tokens=12,
                     images=[img(0.2)])[0]
    c = eng.generate([prompt], temperature=0.0, max_new_tokens=12,
                     images=[img(2.5)])[0]
    assert a.token_ids == b.token_ids          # deterministic
    assert a.token_ids != c.token_ids          # the image conditions output
    assert a.n_prompt_tokens == len(prompt)    # patches count as prompt


def test_mixed_batch_text_rows_unaffected_by_image_rows():
    eng = make_vlm_engine()
    plain = make_vlm_engine()
    tok = eng.tokenizer
    text_prompt = tok.encode("plain text row", add_bos=True)
    vp = vlm_prompt(tok, eng.cfg)
    want = plain.generate([text_prompt], temperature=0.0,
                          max_new_tokens=8)[0]
    got = eng.generate([vp, text_prompt], temperature=0.0, max_new_tokens=8,
                       images=[img(0.4), None])[1]
    assert got.token_ids == want.token_ids


def test_text_only_model_rejects_images():
    from quoracle_tpu.models.config import get_model_config as g
    cfg = g("xla:tiny")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    eng = GenerateEngine(cfg, params, ByteTokenizer(), max_seq=256,
                         prompt_buckets=(32,))
    import pytest
    with pytest.raises(ValueError, match="no vision tower"):
        eng.generate([[1, 2, 3]], images=[img(0.1)])


# ---------------------------------------------------------------------------
# Backend multimodal message path
# ---------------------------------------------------------------------------

def _png_b64(tmp_path, seed=5) -> str:
    rng = np.random.default_rng(seed)
    w = h = 32
    pixels = rng.integers(0, 255, (h * w * 3,), dtype=np.uint8).tobytes()
    path = str(tmp_path / "img.png")
    write_png(path, pixels, w, h)
    with open(path, "rb") as f:
        return base64.b64encode(f.read()).decode()


def test_backend_serves_multimodal_messages(tmp_path):
    backend = TPUBackend(["xla:tiny-vlm"])
    b64 = _png_b64(tmp_path)
    msgs = [{"role": "user", "content": [
        {"type": "text", "text": "what is shown here?"},
        {"type": "image_base64", "data": b64},
    ]}]
    r = backend.query([QueryRequest(model_spec="xla:tiny-vlm",
                                    messages=msgs, temperature=0.0,
                                    max_tokens=8)])[0]
    assert r.ok, r.error
    vc = get_model_config("xla:tiny-vlm").vision
    # the prompt includes one placeholder per patch
    assert r.usage.prompt_tokens > vc.n_patches
    # a different image changes the (greedy) output
    msgs2 = [{"role": "user", "content": [
        {"type": "text", "text": "what is shown here?"},
        {"type": "image_base64", "data": _png_b64(tmp_path, seed=11)},
    ]}]
    r2 = backend.query([QueryRequest(model_spec="xla:tiny-vlm",
                                     messages=msgs2, temperature=0.0,
                                     max_tokens=8)])[0]
    assert r2.ok and r2.text != r.text
    backend.close()


def test_backend_degrades_bad_image_to_text(tmp_path):
    backend = TPUBackend(["xla:tiny-vlm"])
    msgs = [{"role": "user", "content": [
        {"type": "text", "text": "look:"},
        {"type": "image_base64", "data": base64.b64encode(
            b"not a png").decode()},
    ]}]
    r = backend.query([QueryRequest(model_spec="xla:tiny-vlm",
                                    messages=msgs, temperature=0.0,
                                    max_tokens=6)])[0]
    assert r.ok, r.error                      # served as text with [image]
    backend.close()


# ---------------------------------------------------------------------------
# ImageDetector parity: image payloads in action results flow through the
# history → messages pipeline as multimodal parts
# ---------------------------------------------------------------------------

def test_result_images_become_message_parts():
    from quoracle_tpu.context.history import (
        AgentContext, HistoryEntry, RESULT, USER,
    )
    from quoracle_tpu.context.message_builder import build_messages_for_model
    ctx = AgentContext()
    ctx.append("m", HistoryEntry(kind=USER, content="fetch the chart"))
    ctx.append("m", HistoryEntry(kind=RESULT, action_type="fetch_web",
                                 content={"action": "fetch_web", "result": {
                                     "status": "ok",
                                     "content_type": "image/png",
                                     "image_base64": "QUJD",
                                 }}))
    msgs = build_messages_for_model(ctx, "m", system_prompt="sys")
    last = msgs[-1]
    assert isinstance(last["content"], list)
    types = [p["type"] for p in last["content"]]
    assert types == ["text", "image_base64"]
    assert last["content"][1]["data"] == "QUJD"
    # the raw base64 is OUT of the text part; a marker replaces it
    assert "QUJD" not in last["content"][0]["text"]
    assert "[attached image #1]" in last["content"][0]["text"]


def test_injections_append_to_multimodal_messages():
    """TODO/budget/token-count injections must compose with parts content
    (8-step injection order preserved)."""
    from quoracle_tpu.context.history import (
        AgentContext, HistoryEntry, RESULT,
    )
    from quoracle_tpu.context.message_builder import build_messages_for_model
    from quoracle_tpu.context.token_manager import TokenManager
    ctx = AgentContext()
    ctx.append("m", HistoryEntry(kind=RESULT, action_type="fetch_web",
                                 content={"result": {"image_base64": "QUJD"}}))
    ctx.todos = [{"task": "t", "done": False}]
    tm = TokenManager(lambda spec, text: max(1, len(text) // 4),
                      context_limit_fn=lambda spec: 1000)
    msgs = build_messages_for_model(ctx, "m", token_manager=tm)
    content = msgs[-1]["content"]
    assert isinstance(content, list)
    flat = "\n".join(p.get("text", "") for p in content
                     if p.get("type") == "text")
    assert "[CURRENT TODO LIST]" in flat and "[CONTEXT:" in flat
    assert any(p.get("type") == "image_base64" for p in content)


def test_mixed_sessioned_text_and_image_rows_split():
    """A batch mixing a sessioned text row with an image row keeps the text
    row's KV residency (the engine splits the batch internally)."""
    eng = make_vlm_engine()
    tok = eng.tokenizer
    text_p = tok.encode("a sessioned conversation " * 4, add_bos=True)
    r1 = eng.generate([text_p], temperature=0.0, max_new_tokens=6,
                      session_ids=["t"])[0]
    text_p2 = text_p + r1.token_ids + tok.encode(" more")
    vp = vlm_prompt(tok, eng.cfg)
    res = eng.generate([vp, text_p2], temperature=0.0, max_new_tokens=6,
                       session_ids=[None, "t"],
                       images=[img(0.3), None])
    assert len(res) == 2
    # the text row reused its resident prefix despite the image row
    assert res[1].n_cached_tokens > 0
    # the image row produced output and stored no session
    assert res[0].n_gen_tokens > 0
