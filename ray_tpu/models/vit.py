"""ViT image encoder + CLIP dual-tower (BASELINE config 4).

Reference parity: multimodal pipelines ride torch models under Ray Data/
Train in the reference; here ViT/CLIP are native. The encoder reuses the
decoder's block stack (transformer.attention_sublayer with causal=False) —
patchify is a reshape + one einsum, so the whole image tower is matmuls on
the MXU; there is no conv primitive to special-case.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops import cross_entropy_loss
from .transformer import (
    Params,
    TransformerConfig,
    _block,
    _norm,
    init_params as _dense_init,
    logical_axes as _dense_axes,
)


@dataclasses.dataclass(frozen=True)
class ViTConfig:
    image_size: int = 224
    patch_size: int = 16
    channels: int = 3
    d_model: int = 768
    n_layers: int = 12
    n_heads: int = 12
    d_ff: int = 3072
    out_dim: int = 1000  # classes (classifier) or projection dim (CLIP)
    pool: str = "cls"  # "cls" | "mean"
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = False

    @property
    def num_patches(self) -> int:
        return (self.image_size // self.patch_size) ** 2

    @property
    def encoder_config(self) -> TransformerConfig:
        return TransformerConfig(
            vocab_size=1,  # unused: the tower has no token embedding
            d_model=self.d_model,
            n_layers=self.n_layers,
            n_heads=self.n_heads,
            d_ff=self.d_ff,
            max_seq=self.num_patches + 1,
            pos_emb="learned",
            norm="layernorm",
            act="gelu",
            use_bias=True,
            causal=False,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            remat=self.remat,
        )

    def replace(self, **kw) -> "ViTConfig":
        return dataclasses.replace(self, **kw)


def vit_b16() -> ViTConfig:
    return ViTConfig()


def vit_l16() -> ViTConfig:
    return ViTConfig(d_model=1024, n_layers=24, n_heads=16, d_ff=4096)


def vit_tiny() -> ViTConfig:
    return ViTConfig(
        image_size=32,
        patch_size=8,
        d_model=64,
        n_layers=2,
        n_heads=4,
        d_ff=128,
        out_dim=10,
        dtype=jnp.float32,
    )


# ----------------------------------------------------------------------- init


def init_params(config: ViTConfig, key: jax.Array) -> Params:
    c = config
    enc = c.encoder_config
    base = _dense_init(enc, key)
    pd = c.param_dtype
    patch_dim = c.patch_size * c.patch_size * c.channels
    keys = jax.random.split(jax.random.fold_in(key, 7), 4)
    return {
        "patch_proj": (
            (1.0 / math.sqrt(patch_dim)) * jax.random.normal(keys[0], (patch_dim, c.d_model))
        ).astype(pd),
        "patch_bias": jnp.zeros((c.d_model,), pd),
        "cls": (0.02 * jax.random.normal(keys[1], (1, 1, c.d_model))).astype(pd),
        "pos": (0.02 * jax.random.normal(keys[2], (c.num_patches + 1, c.d_model))).astype(pd),
        "blocks": base["blocks"],
        "lnf_scale": base["lnf_scale"],
        "lnf_bias": base["lnf_bias"],
        "head": (0.02 * jax.random.normal(keys[3], (c.d_model, c.out_dim))).astype(pd),
        "head_bias": jnp.zeros((c.out_dim,), pd),
    }


def logical_axes(config: ViTConfig) -> Params:
    base = _dense_axes(config.encoder_config)
    return {
        "patch_proj": (None, "embed"),
        "patch_bias": (None,),
        "cls": (None, None, "embed"),
        "pos": (None, "embed"),
        "blocks": base["blocks"],
        "lnf_scale": (None,),
        "lnf_bias": (None,),
        "head": ("embed", None),
        "head_bias": (None,),
    }


# -------------------------------------------------------------------- forward


def patchify(images: jax.Array, patch: int) -> jax.Array:
    """(B, H, W, C) → (B, N, patch·patch·C), row-major patches."""
    b, h, w, c = images.shape
    gh, gw = h // patch, w // patch
    x = images.reshape(b, gh, patch, gw, patch, c)
    x = x.transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(b, gh * gw, patch * patch * c)


def forward(
    params: Params, images: jax.Array, config: ViTConfig
) -> jax.Array:
    """(B, H, W, C) float images → (B, out_dim)."""
    c = config
    enc = c.encoder_config
    dt = c.dtype
    patches = patchify(images.astype(dt), c.patch_size)
    x = jnp.einsum("bnp,pe->bne", patches, params["patch_proj"].astype(dt))
    x = x + params["patch_bias"].astype(dt)
    cls = jnp.broadcast_to(params["cls"].astype(dt), (x.shape[0], 1, c.d_model))
    x = jnp.concatenate([cls, x], axis=1)
    x = x + params["pos"].astype(dt)[None]

    def block_fn(carry, lp):
        return _block(carry, lp, enc, None, None), None

    if c.remat:
        block_fn = jax.checkpoint(block_fn)
    x, _ = jax.lax.scan(block_fn, x, params["blocks"])
    x = _norm(x, params["lnf_scale"], params["lnf_bias"], "layernorm")
    pooled = x[:, 0] if c.pool == "cls" else jnp.mean(x[:, 1:], axis=1)
    return jnp.einsum("be,eo->bo", pooled, params["head"].astype(dt)) + params[
        "head_bias"
    ].astype(dt)


# ----------------------------------------------------------------------- CLIP


@dataclasses.dataclass(frozen=True)
class CLIPConfig:
    vision: ViTConfig = dataclasses.field(default_factory=vit_b16)
    text: TransformerConfig = dataclasses.field(
        default_factory=lambda: TransformerConfig(
            vocab_size=49408,
            d_model=512,
            n_layers=12,
            n_heads=8,
            d_ff=2048,
            max_seq=77,
            pos_emb="learned",
            norm="layernorm",
            act="gelu",
            causal=True,
            tie_embeddings=False,
        )
    )
    proj_dim: int = 512
    init_logit_scale: float = math.log(1 / 0.07)


def clip_tiny() -> CLIPConfig:
    return CLIPConfig(
        vision=vit_tiny().replace(out_dim=32),
        text=TransformerConfig(
            vocab_size=256,
            d_model=64,
            n_layers=2,
            n_heads=4,
            d_ff=128,
            max_seq=16,
            pos_emb="learned",
            norm="layernorm",
            act="gelu",
            causal=True,
            tie_embeddings=False,
            dtype=jnp.float32,
        ),
        proj_dim=32,
    )


def init_clip_params(config: CLIPConfig, key: jax.Array) -> Params:
    kv, kt, kp = jax.random.split(key, 3)
    vision_cfg = config.vision.replace(out_dim=config.proj_dim)
    text_params = _dense_init(config.text, kt)
    text_params.pop("lm_head", None)
    return {
        "vision": init_params(vision_cfg, kv),
        "text": text_params,
        "text_proj": (
            0.02 * jax.random.normal(kp, (config.text.d_model, config.proj_dim))
        ).astype(config.text.param_dtype),
        "logit_scale": jnp.asarray(config.init_logit_scale, jnp.float32),
    }


def _text_features(
    params: Params, tokens: jax.Array, lengths: jax.Array, config: CLIPConfig
) -> jax.Array:
    """Causal text tower pooled at the last valid token."""
    from .transformer import forward as _text_forward  # reuse trunk via logits? no:

    c = config.text
    dt = c.dtype
    _, s = tokens.shape
    x = params["wte"].astype(dt)[tokens]
    x = x + params["wpe"].astype(dt)[None, :s]

    def block_fn(carry, lp):
        return _block(carry, lp, c, None, None), None

    x, _ = jax.lax.scan(block_fn, x, params["blocks"])
    x = _norm(x, params["lnf_scale"], params.get("lnf_bias"), c.norm, c.norm_eps)
    return jnp.take_along_axis(x, (lengths - 1)[:, None, None], axis=1)[:, 0]


def clip_forward(
    params: Params,
    images: jax.Array,
    tokens: jax.Array,
    lengths: jax.Array,
    config: CLIPConfig,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """→ (image_emb (B,P), text_emb (B,P), logit_scale) — L2-normalized."""
    vision_cfg = config.vision.replace(out_dim=config.proj_dim)
    img = forward(params["vision"], images, vision_cfg).astype(jnp.float32)
    txt = _text_features(params["text"], tokens, lengths, config).astype(jnp.float32)
    txt = txt @ params["text_proj"].astype(jnp.float32)
    img = img / (jnp.linalg.norm(img, axis=-1, keepdims=True) + 1e-8)
    txt = txt / (jnp.linalg.norm(txt, axis=-1, keepdims=True) + 1e-8)
    scale = jnp.exp(jnp.clip(params["logit_scale"], -10.0, math.log(100.0)))
    return img, txt, scale


def clip_loss(
    params: Params,
    images: jax.Array,
    tokens: jax.Array,
    lengths: jax.Array,
    config: CLIPConfig,
) -> jax.Array:
    """Symmetric InfoNCE over the batch."""
    img, txt, scale = clip_forward(params, images, tokens, lengths, config)
    logits = scale * img @ txt.T  # (B, B)
    labels = jnp.arange(logits.shape[0])
    li, _ = cross_entropy_loss(logits, labels)
    lt, _ = cross_entropy_loss(logits.T, labels)
    return 0.5 * (li + lt)
