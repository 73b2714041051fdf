"""Synthetic datasets (deterministic, index-addressable).

FCCO requires every batch element to carry its *global sample index* (the u
estimators are per-sample), so the pipeline yields (indices, batch).

**Index-addressability is a hard contract**: sample i's bytes are a pure
function of (dataset config, i) — ``batch([i])`` equals the i-th row of
``batch(perm)`` for any permutation containing i.  All randomness goes
through the per-sample counter-based generators in ``repro.data.rng``
(Philox keyed on (seed, stream), counter block = global index); the
streaming pipeline (``repro.data.streaming``) re-applies the same
augment helpers at decode time, which is what makes a materialized
shard stream bit-identical to these in-memory datasets.

The contrastive dataset embeds a learnable signal: image i is a fixed random
"prototype" image determined by a latent class, and its caption tokens encode
the same class, so a CLIP model can genuinely align the modalities and
retrieval accuracy is a meaningful metric (used by the paper-claims
benchmarks).
"""
from __future__ import annotations

import dataclasses

import numpy as np

from repro.configs.base import ArchConfig
from repro.data import rng as R


@dataclasses.dataclass
class ContrastiveDataset:
    """n synthetic image-text pairs over ``n_classes`` latent concepts."""
    n: int
    image_size: int
    context_length: int
    vocab_size: int
    n_classes: int = 64
    noise: float = 0.3
    seed: int = 0

    # stream label of the per-sample image-noise augment; the shard
    # writer records it so streaming decode re-derives the same key
    IMAGE_STREAM = "contrastive/images"

    def __post_init__(self):
        rng = np.random.RandomState(self.seed)
        self.classes = rng.randint(0, self.n_classes, size=self.n)
        # class prototypes in a low-dim latent, rendered to image "texture"
        self.protos = rng.randn(self.n_classes, 8, 8, 3).astype(np.float32)
        # caption template: class id spelled in tokens (reserving 0 = BOS)
        self.tok_base = rng.randint(1, self.vocab_size,
                                    size=(self.n_classes, 4))
        self._img_key = R.stream_key(self.seed, self.IMAGE_STREAM)

    def _proto_blocks(self, idx):
        """(b, 8, 1, 8, 1, 3): each sample's 8x8x3 prototype, one value
        for each (up, up) block of its image, up = image_size // 8."""
        return self.protos[self.classes[idx]][:, :, None, :, None, :]

    def clean_images(self, idx):
        """The noise-free rendered prototypes (what the shard writer
        materializes; the noise augment is re-applied at decode)."""
        blocks = self._proto_blocks(np.asarray(idx).reshape(-1))
        b, up = len(blocks), self.image_size // 8
        return np.broadcast_to(blocks, (b, 8, up, 8, up, 3)).reshape(
            b, 8 * up, 8 * up, 3)

    def images(self, idx):
        """``add_gaussian_noise(clean_images(idx), ...)``, bit for bit,
        without rendering the clean images: the prototypes are added into
        the noise by a broadcast over the upsampling blocks."""
        idx = np.asarray(idx).reshape(-1)
        up = self.image_size // 8
        protos = self._proto_blocks(idx)

        def add_protos(lo, hi, block):
            blocks = block.reshape(hi - lo, 8, up, 8, up, 3)
            blocks += protos[lo:hi]
        return R.noise_plus(self.noise, self._img_key, idx,
                            (8 * up, 8 * up, 3), add_protos)

    def texts(self, idx):
        b = len(idx)
        toks = np.zeros((b, self.context_length), np.int32)
        cls_toks = self.tok_base[self.classes[idx]]       # (b, 4)
        reps = min(self.context_length // 4, 4)
        for r in range(reps):
            toks[:, r * 4:(r + 1) * 4] = cls_toks
        return toks

    def batch(self, idx):
        idx = np.asarray(idx)
        return {"images": self.images(idx), "texts": self.texts(idx)}


@dataclasses.dataclass
class ZeroShotEvalDataset:
    """Planted-structure eval split for the zero-shot/retrieval engine.

    Structure (everything exact in f32 — the known-answer contract):

      * ``n_classes`` orthonormal class prototypes: one-hot vectors in the
        8x8x3 = 192-dim image latent, rendered to images by constant-block
        upsampling with **zero noise** — a block-mean downsample recovers
        the prototype bit-exactly;
      * items grouped by class, ``n_per_class`` each (item i has class
        ``i // n_per_class``), captions carry the class token n-gram at
        position 0;
      * ``labels`` equal the planted classes except for an optional
        deterministic fraction of **label-only** flips
        (``label_flip_frac``): the image and caption keep the true class,
        only the reported label lies — so retrieval stays clean while
        zero-shot top-1 becomes exactly ``1 - flip_frac``.

    Under the planted encoder (repro.eval.planted) every eval metric is
    analytically determined — see ``planted.known_answers`` for the
    closed forms (e.g. R@k = min(k, n_per_class) / n_per_class under the
    (score desc, index asc) tie rule).
    """
    n_classes: int = 8
    n_per_class: int = 8
    image_size: int = 32
    context_length: int = 16
    vocab_size: int = 512
    token_len: int = 4
    label_flip_frac: float = 0.0
    seed: int = 0

    LATENT = 8 * 8 * 3

    def __post_init__(self):
        assert self.n_classes <= self.LATENT, "one-hot latent exhausted"
        assert self.image_size % 8 == 0
        assert self.token_len <= self.context_length
        self.n = self.n_classes * self.n_per_class
        self.classes = np.repeat(np.arange(self.n_classes),
                                 self.n_per_class)
        eye = np.eye(self.LATENT, dtype=np.float32)[:self.n_classes]
        self.protos = eye.reshape(self.n_classes, 8, 8, 3)
        rng = np.random.RandomState(self.seed)
        # unique class n-grams (class identity is the contiguous n-gram)
        seen = set()
        rows = []
        while len(rows) < self.n_classes:
            cand = tuple(rng.randint(1, self.vocab_size,
                                     size=self.token_len))
            if cand not in seen:
                seen.add(cand)
                rows.append(cand)
        self.tok_base = np.asarray(rows, np.int32)
        self.labels = self.classes.copy()
        n_flip = int(round(self.label_flip_frac * self.n))
        if n_flip:
            flip_idx = rng.choice(self.n, n_flip, replace=False)
            shift = 1 + rng.randint(0, self.n_classes - 1, n_flip)
            self.labels[flip_idx] = (self.labels[flip_idx] + shift) \
                % self.n_classes

    def images(self, idx):
        base = self.protos[self.classes[idx]]             # (b, 8, 8, 3)
        r = self.image_size // 8
        return np.repeat(np.repeat(base, r, axis=1), r, axis=2)

    def texts(self, idx):
        b = len(idx)
        toks = np.zeros((b, self.context_length), np.int32)
        toks[:, :self.token_len] = self.tok_base[self.classes[idx]]
        return toks

    def batch(self, idx):
        idx = np.asarray(idx)
        return {"images": self.images(idx), "texts": self.texts(idx)}


@dataclasses.dataclass
class LMDataset:
    """Synthetic token stream with learnable bigram structure."""
    n: int
    seq_len: int
    vocab_size: int
    seed: int = 0

    TOKEN_STREAM = "lm/tokens"

    def __post_init__(self):
        rng = np.random.RandomState(self.seed)
        # sparse bigram table: each token has 4 likely successors
        self.next_tok = rng.randint(0, self.vocab_size,
                                    size=(self.vocab_size, 4))
        self._tok_key = R.stream_key(self.seed, self.TOKEN_STREAM)

    def batch(self, idx):
        idx = np.asarray(idx).reshape(-1)
        b = len(idx)
        # per-sample draws: row j's chain depends only on (seed, idx[j])
        first = np.empty((b,), np.int64)
        choice = np.empty((b, self.seq_len), np.int64)
        for j, i in enumerate(idx):
            g = R.sample_generator(self._tok_key, i)
            first[j] = g.integers(0, self.vocab_size)
            choice[j] = g.integers(0, 4, size=self.seq_len)
        toks = np.zeros((b, self.seq_len + 1), np.int64)
        toks[:, 0] = first
        for t in range(self.seq_len):
            toks[:, t + 1] = self.next_tok[toks[:, t], choice[:, t]]
        return {"tokens": toks[:, :-1].astype(np.int32),
                "labels": toks[:, 1:].astype(np.int32)}


@dataclasses.dataclass
class PairedEmbeddingDataset:
    """Stub-modality pairs for the contrastive objective on assigned
    backbones: tokens (text side) + precomputed paired embeddings (image /
    audio side).  Class-correlated so alignment is learnable."""
    n: int
    seq_len: int
    vocab_size: int
    pair_dim: int = 512
    n_classes: int = 64
    seed: int = 0

    EMBED_STREAM = "paired/embeds"
    noise: float = 0.3

    def __post_init__(self):
        rng = np.random.RandomState(self.seed)
        self.classes = rng.randint(0, self.n_classes, size=self.n)
        self.protos = rng.randn(self.n_classes, self.pair_dim).astype(
            np.float32)
        self.tok_base = rng.randint(1, self.vocab_size,
                                    size=(self.n_classes, 8))
        self._emb_key = R.stream_key(self.seed, self.EMBED_STREAM)

    def batch(self, idx):
        idx = np.asarray(idx).reshape(-1)
        b = len(idx)
        cls = self.classes[idx]
        emb = R.add_gaussian_noise(self.protos[cls], self.noise,
                                   self._emb_key, idx)
        toks = np.zeros((b, self.seq_len), np.int32)
        reps = max(1, self.seq_len // 8)
        ct = self.tok_base[cls]
        for r in range(min(reps, 8)):
            toks[:, r * 8:(r + 1) * 8] = ct
        return {"tokens": toks, "labels": np.roll(toks, -1, axis=1),
                "pair_embeds": emb}
