"""Model FLOPs per contrastive pair of a two-tower CLIP train step, from the
configuration's shapes.

Forward plus backward counts as three forwards.  Counted: the patch
embedding, every matrix product of both towers (q, k, v, o, the MLP), the
attention products (q.k and p.v; causal attention counts only the pairs it
needs), the two projections, and the loss's pair products (image i against
text j over the batch).  Recomputation under remat is not counted: it is not
required work.  A multiply-add is 2 FLOPs.
"""
from __future__ import annotations


def _stack(seq: int, width: int, hidden: int, layers: int,
           causal: bool) -> float:
    pairs = seq * (seq + 1) / 2 if causal else seq * seq
    per_layer = (2 * seq * width * 4 * width       # q, k, v, o
                 + 2 * 2 * seq * width * hidden    # MLP in and out
                 + 2 * 2 * pairs * width)          # q.k and p.v
    return layers * per_layer


def image_forward(c: dict) -> float:
    patches = (c["image_size"] // c["patch_size"]) ** 2
    W = c["vision_width"]
    embed = 2 * patches * 3 * c["patch_size"] ** 2 * W
    return (embed + _stack(patches + 1, W, c["vision_mlp_ratio"] * W,
                           c["vision_layers"], causal=False)
            + 2 * W * c["embed_dim"])


def text_forward(c: dict) -> float:
    d = c["text_width"]
    return (_stack(c["context_length"], d, c["text_mlp"], c["text_layers"],
                   causal=True) + 2 * d * c["embed_dim"])


def loss_forward(c: dict, batch: int) -> float:
    """Per pair: one row of the (batch x batch) similarity matrix."""
    return 2 * batch * c["embed_dim"]


def per_pair(c: dict, batch: int) -> float:
    """Forward plus backward FLOPs of one pair in a step of ``batch``."""
    return 3 * (image_forward(c) + text_forward(c) + loss_forward(c, batch))
