"""On-device text-conditioned diffusion image generation.

The reference's generate_images action fans out to HOSTED image models over
HTTPS (reference lib/quoracle/models/image_query.ex:1-12 — Task.async_stream
over configured image models, 60s timeout, cost recording). This module is
the TPU-native equivalent behind the same ``ImageBackend`` seam
(models/images.py): a small pixel-space UNet denoiser + DDIM sampler, fully
jitted — the timestep loop is a ``lax.scan`` over precomputed alphas, conv
stacks run channels-last on the MXU, shapes are static.

Like the LLM pool, the model serves whatever weights it is given: random
init produces textured-noise images (the honest no-network analog of the
generated LLM checkpoints, models/make_checkpoint.py — the serving path,
batching, cost accounting, and determinism are real; picture quality needs
trained weights, which need a network). Weights load/store as a flat
pytree, so a trained checkpoint drops in without code changes.
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib
import os
import time
import uuid
from typing import Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from quoracle_tpu.models.images import GeneratedImage, ImageBackend, write_png


@dataclasses.dataclass(frozen=True)
class DiffusionConfig:
    image_size: int = 64          # model output; host resizes to request
    base_ch: int = 48
    ch_mult: tuple = (1, 2, 4)
    emb_ch: int = 192             # time + text embedding width
    vocab_size: int = 512         # prompt tokens (byte-level)
    groups: int = 8
    train_steps: int = 1000      # beta schedule length
    sample_steps: int = 30       # DDIM steps per image


def _conv_init(key, kh, kw, cin, cout):
    fan_in = kh * kw * cin
    return (jax.random.normal(key, (kh, kw, cin, cout), jnp.float32)
            * fan_in ** -0.5)


def init_diffusion_params(cfg: DiffusionConfig, key: jax.Array) -> dict:
    ks = iter(jax.random.split(key, 64))
    C = cfg.base_ch
    chans = [C * m for m in cfg.ch_mult]

    def res(cin, cout):
        return {
            "n1": jnp.ones((cin,)), "c1": _conv_init(next(ks), 3, 3, cin,
                                                     cout),
            "temb": (jax.random.normal(next(ks), (cfg.emb_ch, cout))
                     * cfg.emb_ch ** -0.5),
            "n2": jnp.ones((cout,)), "c2": _conv_init(next(ks), 3, 3, cout,
                                                      cout),
            "skip": (_conv_init(next(ks), 1, 1, cin, cout)
                     if cin != cout else None),
        }

    p = {
        "text_embed": (jax.random.normal(next(ks),
                                         (cfg.vocab_size, cfg.emb_ch))
                       * cfg.emb_ch ** -0.5),
        "temb_w1": (jax.random.normal(next(ks), (cfg.emb_ch, cfg.emb_ch))
                    * cfg.emb_ch ** -0.5),
        "temb_w2": (jax.random.normal(next(ks), (cfg.emb_ch, cfg.emb_ch))
                    * cfg.emb_ch ** -0.5),
        "stem": _conv_init(next(ks), 3, 3, 3, chans[0]),
        "down": [], "downs": [],
        "mid": res(chans[-1], chans[-1]),
        "up": [], "ups": [],
        "out_n": jnp.ones((chans[0],)),
        "out_c": _conv_init(next(ks), 3, 3, chans[0], 3) * 0.1,
    }
    for i in range(len(chans) - 1):
        p["down"].append(res(chans[i], chans[i]))
        p["downs"].append(_conv_init(next(ks), 3, 3, chans[i], chans[i + 1]))
    for i in range(len(chans) - 1, 0, -1):
        p["ups"].append(_conv_init(next(ks), 3, 3, chans[i], chans[i - 1]))
        p["up"].append(res(2 * chans[i - 1], chans[i - 1]))
    return p


def _gn(x, w, groups):
    """GroupNorm (no bias), channels-last [B, H, W, C]."""
    B, H, W, C = x.shape
    g = x.reshape(B, H, W, groups, C // groups)
    mu = jnp.mean(g, axis=(1, 2, 4), keepdims=True)
    var = jnp.var(g, axis=(1, 2, 4), keepdims=True)
    g = (g - mu) * jax.lax.rsqrt(var + 1e-5)
    return g.reshape(B, H, W, C) * w


def _conv(x, w, stride=1):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _resblock(x, p, temb, groups):
    h = _conv(jax.nn.silu(_gn(x, p["n1"], groups)), p["c1"])
    h = h + (temb @ p["temb"])[:, None, None, :]
    h = _conv(jax.nn.silu(_gn(h, p["n2"], groups)), p["c2"])
    if p["skip"] is not None:
        x = _conv(x, p["skip"])
    return x + h


def _upsample(x):
    B, H, W, C = x.shape
    return jax.image.resize(x, (B, 2 * H, 2 * W, C), "nearest")


def denoise(params: dict, cfg: DiffusionConfig, x: jax.Array,
            t: jax.Array, text_emb: jax.Array) -> jax.Array:
    """Predict noise eps for x_t. x [B, S, S, 3]; t [B] in [0, 1);
    text_emb [B, emb_ch]."""
    half = cfg.emb_ch // 2
    freqs = jnp.exp(-jnp.arange(half) / half * 9.21)      # 1 .. 1e-4
    ang = t[:, None] * cfg.train_steps * freqs[None, :]
    temb = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=1)
    temb = jax.nn.silu(temb @ params["temb_w1"]) + text_emb
    temb = jax.nn.silu(temb @ params["temb_w2"])

    h = _conv(x, params["stem"])
    skips = []
    for rb, dw in zip(params["down"], params["downs"]):
        h = _resblock(h, rb, temb, cfg.groups)
        skips.append(h)
        h = _conv(h, dw, stride=2)
    h = _resblock(h, params["mid"], temb, cfg.groups)
    for rb, uw in zip(params["up"], params["ups"]):
        h = _conv(_upsample(h), uw)
        h = jnp.concatenate([h, skips.pop()], axis=-1)
        h = _resblock(h, rb, temb, cfg.groups)
    return _conv(jax.nn.silu(_gn(h, params["out_n"], cfg.groups)),
                 params["out_c"])


@functools.partial(jax.jit, static_argnames=("cfg",))
def ddim_sample(params: dict, cfg: DiffusionConfig, prompt_ids: jax.Array,
                rng: jax.Array) -> jax.Array:
    """DDIM sampling loop (lax.scan over the step schedule, one compiled
    denoiser body). prompt_ids [B, T] int32 (0-padded) → images
    [B, S, S, 3] in [0, 1]."""
    B = prompt_ids.shape[0]
    emb = params["text_embed"][prompt_ids]               # [B, T, E]
    nz = (prompt_ids > 0).astype(jnp.float32)[..., None]
    text_emb = (emb * nz).sum(1) / jnp.maximum(nz.sum(1), 1.0)

    betas = jnp.linspace(1e-4, 0.02, cfg.train_steps)
    abar = jnp.cumprod(1.0 - betas)
    idx = jnp.linspace(cfg.train_steps - 1, 0,
                       cfg.sample_steps).astype(jnp.int32)
    a_t = abar[idx]
    a_prev = jnp.concatenate([abar[idx[1:]], jnp.ones((1,))])

    x0 = jax.random.normal(rng, (B, cfg.image_size, cfg.image_size, 3))

    def step(x, sched):
        t_i, a, ap = sched
        eps = denoise(params, cfg, x, jnp.full((B,), t_i / cfg.train_steps),
                      text_emb)
        x0_pred = (x - jnp.sqrt(1.0 - a) * eps) * jax.lax.rsqrt(a)
        x0_pred = jnp.clip(x0_pred, -3.0, 3.0)
        x = jnp.sqrt(ap) * x0_pred + jnp.sqrt(1.0 - ap) * eps
        return x, None

    x, _ = jax.lax.scan(step, x0, (idx.astype(jnp.float32), a_t, a_prev))
    return jnp.clip(x * 0.5 + 0.5, 0.0, 1.0)


class DiffusionImageBackend(ImageBackend):
    """ImageBackend serving the in-tree diffusion model on-device.

    Prompt conditioning uses byte-level token ids (same id scheme as
    ByteTokenizer) so no tokenizer asset is required; per-image seeds are
    prompt-derived and deterministic, matching the procedural backend's
    reproducibility contract.
    """

    def __init__(self, cfg: Optional[DiffusionConfig] = None,
                 params: Optional[dict] = None, seed: int = 0,
                 models: Sequence[str] = ("xla:diffusion-v0",),
                 cost_per_image: float = 0.0):
        self.cfg = cfg or DiffusionConfig()
        self.params = (params if params is not None
                       else init_diffusion_params(self.cfg,
                                                  jax.random.PRNGKey(seed)))
        self.models = list(models)
        self.cost_per_image = cost_per_image

    def _prompt_ids(self, prompt: str, max_len: int = 64) -> np.ndarray:
        ids = [min(b + 3, self.cfg.vocab_size - 1)
               for b in prompt.encode("utf-8")[:max_len]]
        out = np.zeros((max_len,), np.int32)
        out[:len(ids)] = ids
        return out

    def generate(self, prompt: str, *, count: int = 1,
                 size: str = "256x256",
                 out_dir: Optional[str] = None) -> list[GeneratedImage]:
        try:
            w, h = (int(x) for x in size.lower().split("x"))
        except ValueError:
            raise ValueError(f"bad size {size!r}; expected WxH")
        w, h = max(8, min(w, 1024)), max(8, min(h, 1024))
        out_dir = out_dir or "/tmp"
        os.makedirs(out_dir, exist_ok=True)
        n = max(1, min(count, 8))
        seed = int.from_bytes(
            hashlib.sha256(prompt.encode()).digest()[:4], "big")
        ids = jnp.asarray(np.stack([self._prompt_ids(prompt)] * n))
        imgs = ddim_sample(self.params, self.cfg, ids,
                           jax.random.PRNGKey(seed))
        imgs = np.asarray(imgs)                          # [n, S, S, 3]
        out = []
        for i in range(n):
            # nearest-neighbor resize to the requested size host-side
            S = self.cfg.image_size
            yi = (np.arange(h) * S // h)
            xi = (np.arange(w) * S // w)
            px = (imgs[i][yi][:, xi] * 255).astype(np.uint8)
            path = os.path.join(
                out_dir,
                f"img-{uuid.uuid4().hex[:10]}-{int(time.time())}.png")
            write_png(path, px.tobytes(), w, h)
            out.append(GeneratedImage(
                path=path, model=self.models[i % len(self.models)],
                width=w, height=h, cost=self.cost_per_image))
        return out
