"""Two-tower CLIP model (the paper's own architectures).

Text tower: 12-layer pre-norm transformer (causal, as in CLIP), pooled at
the last token.  Vision tower: ViT or ResNet50 per config.  Returns
*unnormalized* embeddings; L2 normalization happens in the loss layer
(repro.core) so its gradient is part of the contrastive VJP.

Both towers take ``impl`` (attention implementation: "chunked"/"flash"/
"naive") and ``precision`` (mixed-precision policy, models.precision):
with ``bf16`` the tower matmuls/activations run in bf16 while params stay
f32 masters and the embeddings are cast back to f32 at the tower exit —
the loss layer (l2_normalize + the exact LSE engine) is always f32.

Each tower runs under its ``repro.tracing`` scope (``image_tower``,
``text_tower``), so every caller's HLO (train step, eval, serving)
names its operations by tower.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import tracing as TR
from repro.configs.base import ArchConfig
from repro.models import layers as L
from repro.models import precision as PR
from repro.models import transformer as T
from repro.models import vit as V
from repro.models import resnet as R


def init_clip(rng, cfg: ArchConfig):
    c = cfg.clip
    r = L.split_rngs(rng, 5)
    if c.vision_arch == "vit":
        vision = V.init_vit(r[0], c)
    elif c.vision_arch == "resnet":
        vision = R.init_resnet(r[0], c)
    else:
        raise ValueError(c.vision_arch)
    return {
        "vision": vision,
        "tok_embed": L.embed_init(r[1], cfg.vocab_size, cfg.d_model),
        "pos_embed": jax.random.normal(r[2], (1, c.context_length,
                                              cfg.d_model)) * 0.01,
        "text_blocks": T.init_stack(r[3], cfg, cfg.n_layers, mlp="gelu"),
        "text_norm": L.init_rmsnorm(cfg.d_model),
        "text_proj": L.dense_init(r[4], cfg.d_model, c.embed_dim),
    }


def encode_image(params, cfg: ArchConfig, images, *, impl="chunked",
                 precision=PR.F32):
    c = cfg.clip
    with jax.named_scope(TR.IMAGE_TOWER):
        if c.vision_arch == "vit":
            return V.apply_vit(params["vision"], c, images, impl=impl,
                               precision=precision)
        # ResNet has no attention; impl is a no-op for it by design.
        return R.apply_resnet(params["vision"], c, images,
                              precision=precision)


def encode_text(params, cfg: ArchConfig, tokens, *, impl="chunked",
                precision=PR.F32):
    """tokens: (B, S) int32 with S <= context_length; shorter inputs
    (token-length curriculum, repro.data.curriculum) use the positional-
    embedding prefix."""
    with jax.named_scope(TR.TEXT_TOWER):
        x = L.embed_tokens(params["tok_embed"], tokens,
                           dtype=precision.compute_dtype)
        x = x + params["pos_embed"][:, :x.shape[1]].astype(x.dtype)
        x = T.apply_stack(params["text_blocks"], cfg, x, mlp="gelu",
                          impl=impl, precision=precision)
        x = L.rmsnorm(params["text_norm"], x)
        # last token (synthetic data: fixed-length captions)
        pooled = x[:, -1]
        out = jnp.einsum("bd,de->be", pooled,
                         params["text_proj"].astype(x.dtype))
        return PR.cast_output(precision, out)


def encode_pair(params, cfg: ArchConfig, batch, *, impl="chunked",
                precision=PR.F32):
    """batch: {"images": (B,H,W,3), "texts": (B,ctx)} ->
    (e1 (B,E), e2 (B,E)) unnormalized image/text embeddings (cast to the
    policy output dtype — f32 — at the tower exits)."""
    e1 = encode_image(params, cfg, batch["images"], impl=impl,
                      precision=precision)
    e2 = encode_text(params, cfg, batch["texts"], impl=impl,
                     precision=precision)
    return e1, e2
