"""Plain float32 reference of one FastCLIP-v3 train step of a two-tower CLIP.

Straightforward ``jax.numpy`` at ``Precision.HIGHEST``, written from the
published description and the configuration file alone; it imports nothing
of the system under test.  It derives its weights from the seed by the same
key tree the system documents for its initialiser (so both start from the
same point), and follows the system's towers where they depart from the
published CLIP; the configuration file lists those departures.

The towers run in blocks of rows so that a step fits beside what the
benchmark keeps on the device: a forward pass over all rows gives the
embeddings, the contrastive loss gives their cotangents, and each block's
parameter gradient is the VJP of its rows with those cotangents.  That is the
same gradient as one backward pass over the whole batch.
"""
from __future__ import annotations

import json
import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
sg = jax.lax.stop_gradient


def _mm(spec, a, b):
    return jnp.einsum(spec, a, b, precision=HI)


# ---------------------------------------------------------------------------
# weights from the seed
# ---------------------------------------------------------------------------

def _dense(key, din, dout, scale=None):
    scale = scale if scale is not None else 1.0 / np.sqrt(din)
    return jax.random.normal(key, (din, dout), dtype=jnp.float32) * scale


def _attn_init(key, d, heads):
    r = jax.random.split(key, 4)
    return {"wq": _dense(r[0], d, d), "wk": _dense(r[1], d, d),
            "wv": _dense(r[2], d, d),
            "wo": _dense(r[3], d, d, scale=1.0 / np.sqrt(d))}


def _mlp_init(key, d, hidden):
    r1, r2 = jax.random.split(key, 2)
    return {"w_in": _dense(r1, d, hidden),
            "b_in": jnp.zeros((hidden,), jnp.float32),
            "w_out": _dense(r2, hidden, d),
            "b_out": jnp.zeros((d,), jnp.float32)}


def _ln_init(d):
    return {"scale": jnp.ones((d,), jnp.float32),
            "bias": jnp.zeros((d,), jnp.float32)}


def init_params(key, c):
    """Weights of the configuration ``c`` (a config file's dict)."""
    r = jax.random.split(key, 5)
    W, d = c["vision_width"], c["text_width"]
    n_patch = (c["image_size"] // c["patch_size"]) ** 2
    rv = jax.random.split(r[0], 4 + c["vision_layers"])

    def vit_block(k):
        k1, k2 = jax.random.split(k)
        return {"n1": _ln_init(W), "attn": _attn_init(k1, W, c["vision_heads"]),
                "n2": _ln_init(W),
                "mlp": _mlp_init(k2, W, c["vision_mlp_ratio"] * W)}

    def text_block(k):
        rk = jax.random.split(k, 4)
        return {"n1": {"scale": jnp.ones((d,), jnp.float32)},
                "attn": _attn_init(rk[0], d, c["text_heads"]),
                "n2": {"scale": jnp.ones((d,), jnp.float32)},
                "mlp": _mlp_init(rk[1], d, c["text_mlp"])}

    vision = {
        "patch": _dense(rv[0], 3 * c["patch_size"] ** 2, W),
        "cls": jax.random.normal(rv[1], (1, 1, W)) * 0.02,
        "pos": jax.random.normal(rv[2], (1, n_patch + 1, W)) * 0.02,
        "blocks": jax.vmap(vit_block)(
            jax.random.split(rv[3], c["vision_layers"])),
        "final_norm": _ln_init(W),
        "proj": _dense(rv[4], W, c["embed_dim"]),
    }
    return {
        "vision": vision,
        "tok_embed": jax.random.normal(
            r[1], (c["vocab_size"], d), dtype=jnp.float32) * 0.02,
        "pos_embed": jax.random.normal(
            r[2], (1, c["context_length"], d)) * 0.01,
        "text_blocks": jax.vmap(text_block)(
            jax.random.split(r[3], c["text_layers"])),
        "text_norm": {"scale": jnp.ones((d,), jnp.float32)},
        "text_proj": _dense(r[4], d, c["embed_dim"]),
    }


# ---------------------------------------------------------------------------
# towers
# ---------------------------------------------------------------------------

def _layernorm(p, x, eps=1e-5):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _rmsnorm(p, x, eps=1e-6):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * p["scale"]


def _rope(x, theta):
    """Rotary positions 0..S-1 on (B, S, H, hd), halves rotated."""
    S, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float32) / hd))
    ang = np.arange(S, dtype=np.float32)[:, None] * freqs
    cos = jnp.asarray(np.cos(ang))[:, None, :]
    sin = jnp.asarray(np.sin(ang))[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(p, x, heads, causal, theta):
    B, S, D = x.shape
    hd = D // heads
    q = _mm("bsd,de->bse", x, p["wq"]).reshape(B, S, heads, hd)
    k = _mm("bsd,de->bse", x, p["wk"]).reshape(B, S, heads, hd)
    v = _mm("bsd,de->bse", x, p["wv"]).reshape(B, S, heads, hd)
    q, k = _rope(q, theta), _rope(k, theta)
    s = _mm("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
    if causal:
        s = jnp.where(np.tril(np.ones((S, S), bool)), s, -jnp.inf)
    o = _mm("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
    return _mm("bse,ed->bsd", o.reshape(B, S, D), p["wo"])


def _mlp(p, x):
    h = jax.nn.gelu(_mm("bsd,df->bsf", x, p["w_in"]) + p["b_in"],
                    approximate=True)
    return _mm("bsf,fd->bsd", h, p["w_out"]) + p["b_out"]


def _stack(block, x, blocks):
    body = jax.checkpoint(lambda h, p: (block(p, h), None))
    return jax.lax.scan(body, x, blocks)[0]


def image_tower(params, c, images):
    """(B, H, W, 3) -> (B, embed_dim): ViT, CLS pooled."""
    v, P = params["vision"], c["patch_size"]
    B, H, W_, _ = images.shape
    x = images.reshape(B, H // P, P, W_ // P, P, 3).transpose(0, 1, 3, 2, 4, 5)
    x = _mm("bpk,kw->bpw", x.reshape(B, -1, 3 * P * P), v["patch"])
    cls = jnp.broadcast_to(v["cls"], (B, 1, x.shape[-1]))
    x = jnp.concatenate([cls, x], 1) + v["pos"]

    def block(p, h):
        h = h + _attention(p["attn"], _layernorm(p["n1"], h),
                           c["vision_heads"], False, c["vision_rope_theta"])
        return h + _mlp(p["mlp"], _layernorm(p["n2"], h))

    x = _layernorm(v["final_norm"], _stack(block, x, v["blocks"]))
    return _mm("bw,we->be", x[:, 0], v["proj"])


def text_tower(params, c, tokens):
    """(B, S) int -> (B, embed_dim): causal pre-RMSNorm stack, last position
    pooled."""
    x = params["tok_embed"][tokens] + params["pos_embed"][:, :tokens.shape[1]]

    def block(p, h):
        h = h + _attention(p["attn"], _rmsnorm(p["n1"], h), c["text_heads"],
                           True, c["text_rope_theta"])
        return h + _mlp(p["mlp"], _rmsnorm(p["n2"], h))

    x = _rmsnorm(params["text_norm"], _stack(block, x, params["text_blocks"]))
    return _mm("bd,de->be", x[:, -1], params["text_proj"])


# ---------------------------------------------------------------------------
# FastCLIP v3: FCCO loss, temperature update, AdamW
# ---------------------------------------------------------------------------

def fcco_v3(e1, e2, u1, u2, tau, gamma, eps, rho):
    """The v3 (RGCL-g) step on one batch, in the linear domain.

    Returns (surrogate, aux): the surrogate's gradient w.r.t. the raw
    embeddings is FastCLIP's estimator; aux holds the new u rows, the
    closed-form temperature gradient (paper eq. 10) and the reported loss
    value tau * mean(log(eps+u1) + log(eps+u2)) + 2 rho tau."""
    e1 = e1 / jnp.maximum(jnp.linalg.norm(e1, axis=-1, keepdims=True), 1e-8)
    e2 = e2 / jnp.maximum(jnp.linalg.norm(e2, axis=-1, keepdims=True), 1e-8)
    B = e1.shape[0]
    s = _mm("id,jd->ij", e1, e2)              # s[i, j] = image i . text j
    sd = jnp.diagonal(s)
    off = 1.0 - jnp.eye(B)
    d1 = s - sd[:, None]                      # image anchor i vs texts j
    d2 = s.T - sd[:, None]                    # text anchor i vs images j
    h1 = jnp.exp(d1 / tau) * off
    h2 = jnp.exp(d2 / tau) * off
    g1 = jnp.sum(h1, 1) / (B - 1)
    g2 = jnp.sum(h2, 1) / (B - 1)
    u1n = (1.0 - gamma) * u1 + gamma * sg(g1)
    u2n = (1.0 - gamma) * u2 + gamma * sg(g2)
    w1, w2 = sg(tau / (eps + u1n)), sg(tau / (eps + u2n))
    loss = jnp.mean(w1 * g1 + w2 * g2)
    dg1 = sg(jnp.sum(h1 * -d1, 1) / ((B - 1) * tau ** 2))
    dg2 = sg(jnp.sum(h2 * -d2, 1) / ((B - 1) * tau ** 2))
    L1, L2 = jnp.log(eps + u1n), jnp.log(eps + u2n)
    tau_grad = (jnp.mean(L1 + L2) + 2 * rho
                + tau * jnp.mean(dg1 / (eps + u1n) + dg2 / (eps + u2n)))
    value = tau * jnp.mean(L1 + L2) + 2 * rho * tau
    return loss, {"u1": u1n, "u2": u2n, "tau_grad": sg(tau_grad),
                  "loss_value": sg(value)}


def lr_at(h, step):
    """Linear warm-up to ``lr`` then cosine decay to 0 over ``steps``."""
    warm = min(500, h["steps"] // 10 + 1)
    if step < warm:
        return h["lr"] * step / warm
    frac = min(max((step - warm) / max(h["steps"] - warm, 1), 0.0), 1.0)
    return 0.5 * (1.0 + math.cos(math.pi * frac)) * h["lr"]


def gamma_at(h, step):
    """The inner rate: cosine over epochs from 1 to gamma_min."""
    epoch = step // h["steps_per_epoch"]
    frac = min(epoch / h["gamma_decay_epochs"], 1.0)
    return (0.5 * (1.0 + math.cos(math.pi * frac)) * (1.0 - h["gamma_min"])
            + h["gamma_min"])


def adamw(p, g, m, v, t, lr, wd, b1=0.9, b2=0.999, eps=1e-8):
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    mh = m / (1 - b1 ** t)
    vh = v / (1 - b2 ** t)
    return p - lr * (mh / (jnp.sqrt(vh) + eps) + wd * p), m, v


_PROGRAMS: dict = {}


def _programs(c, h, rows):
    """The reference's jitted pieces for configuration ``c``,
    hyperparameters ``h`` and blocks of ``rows``: the weights from the
    seed, the embeddings of every row, the loss with the embeddings'
    cotangents, and the gradient with the AdamW update."""

    def blocks(x):
        return x.reshape((x.shape[0] // rows, rows) + x.shape[1:])

    def embed(params, images, tokens):
        return (image_tower(params, c, images),
                text_tower(params, c, tokens))

    def init(key):
        params = init_params(key, c)
        zeros = jax.tree.map(jnp.zeros_like, params)
        return params, zeros, jax.tree.map(jnp.zeros_like, params)

    def embed_all(params, images, tokens):
        e1, e2 = jax.lax.map(lambda b: embed(params, *b),
                             (blocks(images), blocks(tokens)))
        return e1.reshape(-1, e1.shape[-1]), e2.reshape(-1, e2.shape[-1])

    def loss(e1, e2, u1, u2, tau, gamma):
        (_, aux), (de1, de2) = jax.value_and_grad(
            lambda a, b: fcco_v3(a, b, u1, u2, tau, gamma, h["eps"],
                                 h["rho"]), argnums=(0, 1), has_aux=True)(
                                     e1, e2)
        return de1, de2, aux

    def train(params, m, v, images, tokens, de1, de2, t, lr):
        def add_block(acc, b):
            _, pull = jax.vjp(lambda p: embed(p, *b[:2]), params)
            return jax.tree.map(jnp.add, acc, pull(b[2:])[0]), None

        grads = jax.lax.scan(add_block, jax.tree.map(jnp.zeros_like, params),
                             tuple(map(blocks, (images, tokens, de1, de2))))[0]
        out = jax.tree.map(
            lambda p, g, m_, v_: adamw(p, g, m_, v_, t, lr, h["wd"]),
            params, grads, m, v)
        pick = lambda i: jax.tree.map(lambda x: x[i], out,  # noqa: E731
                                      is_leaf=lambda x: isinstance(x, tuple))
        return pick(0), pick(1), pick(2)

    return tuple(jax.jit(f) for f in (init, embed_all, loss, train))


class Reference:
    """The reference trainer: ``step(batch, idx)`` runs one step and returns
    its reported loss value; ``params``, ``m`` hold the state."""

    def __init__(self, c, hyper, seed, rows_per_block=128):
        self.c, self.h = c, hyper
        rows = rows_per_block if c["global_batch"] % rows_per_block == 0 \
            else c["global_batch"]
        key = json.dumps([c, hyper, rows], sort_keys=True)
        if key not in _PROGRAMS:
            _PROGRAMS[key] = _programs(c, hyper, rows)
        init, self._embed, self._loss, self._train = _PROGRAMS[key]
        with jax.default_matmul_precision("highest"):
            self.params, self.m, self.v = init(jax.random.PRNGKey(seed))
        self.u1, self.u2 = {}, {}
        self.tau = float(hyper["tau_init"])
        self.tau_m = self.tau_v = 0.0
        self.t = 0

    def step(self, batch, idx):
        h = self.h
        images, tokens = batch["images"], batch["texts"]
        idx = np.asarray(idx).tolist()
        with jax.default_matmul_precision("highest"):
            e1, e2 = self._embed(self.params, images, tokens)
            u1 = np.asarray([self.u1.get(i, 0.0) for i in idx], np.float32)
            u2 = np.asarray([self.u2.get(i, 0.0) for i in idx], np.float32)
            de1, de2, aux = self._loss(e1, e2, u1, u2, self.tau,
                                       gamma_at(h, self.t))
            self.t += 1
            self.params, self.m, self.v = self._train(
                self.params, self.m, self.v, images, tokens, de1, de2,
                float(self.t), lr_at(h, self.t - 1))
        aux = jax.device_get(aux)
        for i, a, b in zip(idx, aux["u1"], aux["u2"]):
            self.u1[i], self.u2[i] = float(a), float(b)
        self._tau_step(float(aux["tau_grad"]))
        return float(aux["loss_value"])

    def _tau_step(self, g, b1=0.9, b2=0.999, eps=1e-8):
        """Adam on the global temperature, no weight decay; its rate drops
        to a third once tau is under ``tau_lr_decay_at``."""
        h = self.h
        self.tau_m = b1 * self.tau_m + (1 - b1) * g
        self.tau_v = b2 * self.tau_v + (1 - b2) * g * g
        mh = self.tau_m / (1 - b1 ** self.t)
        vh = self.tau_v / (1 - b2 ** self.t)
        lr = h["lr_tau"] / (3.0 if self.tau < h["tau_lr_decay_at"] else 1.0)
        self.tau = max(self.tau - lr * mh / (math.sqrt(vh) + eps),
                       h["tau_min"])


# ---------------------------------------------------------------------------
# the synthetic data layer: index plan and sample contents
# ---------------------------------------------------------------------------

def index_plan(n: int, batch: int, seed: int, step: int) -> np.ndarray:
    """Sample indices of ``step`` (within the first epoch) on one shard: a
    PCG64 permutation of [0, n) keyed on (seed, epoch 0, shard 0)."""
    ss = np.random.SeedSequence(seed, spawn_key=(0, 0))
    perm = np.random.Generator(np.random.PCG64(ss)).permutation(n)
    return perm[step * batch:(step + 1) * batch]


def synthetic_pairs(d: dict, c: dict, n: int, idx) -> dict:
    """The pairs ``idx`` of the synthetic dataset ``d`` (a traffic file's
    ``data``): image i is its class prototype (an 8x8x3 latent, upsampled)
    plus ``noise`` times standard normals drawn from the Philox counter block
    [0, 0, 0, i] of the image stream's key; its caption repeats the class's
    4 tokens ``caption_reps`` times (fewer where the context is shorter),
    then zeros."""
    import zlib
    rng = np.random.RandomState(d["seed"])
    classes = rng.randint(0, d["n_classes"], size=n)
    protos = rng.randn(d["n_classes"], 8, 8, 3).astype(np.float32)
    words = rng.randint(1, c["vocab_size"], size=(d["n_classes"], 4))
    key = np.random.SeedSequence(
        [d["seed"] & 0xFFFFFFFF, zlib.crc32(d["image_stream"].encode())]
    ).generate_state(2, np.uint64)
    S, up = c["image_size"], c["image_size"] // 8
    images = np.empty((len(idx), S, S, 3), np.float32)
    for j, i in enumerate(np.asarray(idx).tolist()):
        noise = np.random.Generator(np.random.Philox(
            key=key, counter=[0, 0, 0, i])).standard_normal(
                (S, S, 3), dtype=np.float32)
        base = protos[classes[i]].repeat(up, axis=0).repeat(up, axis=1)
        images[j] = base + np.float32(d["noise"]) * noise
    texts = np.zeros((len(idx), c["context_length"]), np.int32)
    for r in range(min(c["context_length"] // 4, d["caption_reps"])):
        texts[:, 4 * r:4 * r + 4] = words[classes[np.asarray(idx)]]
    return {"images": images, "texts": texts}
