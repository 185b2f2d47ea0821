"""Vision Transformer (ViT) family — the transformer-era counterpart of
the reference's CNN zoo (the reference imports torchvision/keras models;
its own zoo stops at ResNet/VGG/Inception, so ViT is beyond-parity model
breadth built from this repo's own attention stack).

TPU-first choices:
* Patchify as a single strided conv ([P,P] kernel, stride P) — one big
  MXU contraction, no gather/reshape shuffle.
* Attention through :func:`horovod_tpu.parallel.flash_attention` (the
  pallas kernel) for long sequences, dense for tiny ones — chosen by
  ``attn_impl``.
* bfloat16 compute / float32 params via ``dtype=jnp.bfloat16`` (MXU
  native), pre-LN blocks (stable without warmup tricks), learned
  position embeddings, mean-pool head (no CLS token: a masked-token
  readout adds a ragged access XLA can't fuse as well as a reduce).
"""

from __future__ import annotations

import flax.linen as nn
import jax.numpy as jnp


class _Attention(nn.Module):
    n_heads: int
    dtype: jnp.dtype = jnp.float32
    attn_impl: str = "dense"        # "dense" | "flash"

    @nn.compact
    def __call__(self, x):
        b, l, d = x.shape
        head_dim = d // self.n_heads
        qkv = nn.Dense(3 * d, dtype=self.dtype, name="qkv")(x)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(b, l, self.n_heads, head_dim)
        k = k.reshape(b, l, self.n_heads, head_dim)
        v = v.reshape(b, l, self.n_heads, head_dim)
        if self.attn_impl == "flash":
            from horovod_tpu.parallel.flash_attention import flash_attention

            # Bidirectional (causal=False): every patch attends to all.
            out = flash_attention(q, k, v, causal=False)
        elif self.attn_impl != "dense":
            # Same contract as models/llama.py: an unknown impl raises —
            # a typo must not silently run dense attention.
            raise ValueError(
                f"unknown attn_impl {self.attn_impl!r}; "
                f"expected 'dense' or 'flash'")
        else:
            scores = jnp.einsum(
                "blhd,bmhd->bhlm", q, k
            ) / jnp.sqrt(jnp.asarray(head_dim, self.dtype))
            probs = nn.softmax(scores.astype(jnp.float32), axis=-1)
            out = jnp.einsum("bhlm,bmhd->blhd", probs.astype(self.dtype), v)
        out = out.reshape(b, l, d)
        return nn.Dense(d, dtype=self.dtype, name="proj")(out)


class _Block(nn.Module):
    n_heads: int
    mlp_ratio: int = 4
    dtype: jnp.dtype = jnp.float32
    attn_impl: str = "dense"

    @nn.compact
    def __call__(self, x):
        d = x.shape[-1]
        h = nn.LayerNorm(dtype=self.dtype, name="ln1")(x)
        x = x + _Attention(self.n_heads, self.dtype, self.attn_impl,
                           name="attn")(h)
        h = nn.LayerNorm(dtype=self.dtype, name="ln2")(x)
        h = nn.Dense(self.mlp_ratio * d, dtype=self.dtype, name="fc1")(h)
        h = nn.gelu(h)
        h = nn.Dense(d, dtype=self.dtype, name="fc2")(h)
        return x + h


class ViT(nn.Module):
    """Patchify → pre-LN transformer encoder → mean-pool → linear head."""

    patch: int = 16
    dim: int = 768
    depth: int = 12
    n_heads: int = 12
    num_classes: int = 1000
    dtype: jnp.dtype = jnp.float32
    attn_impl: str = "dense"

    @nn.compact
    def __call__(self, x, train: bool = True):
        del train                    # no dropout/BN: API parity with ResNet
        x = x.astype(self.dtype)
        x = nn.Conv(self.dim, (self.patch, self.patch),
                    strides=(self.patch, self.patch),
                    dtype=self.dtype, name="patchify")(x)
        b, hh, ww, d = x.shape
        x = x.reshape(b, hh * ww, d)
        pos = self.param("pos_embed", nn.initializers.normal(0.02),
                         (1, hh * ww, d), jnp.float32)
        x = x + pos.astype(self.dtype)
        for i in range(self.depth):
            x = _Block(self.n_heads, dtype=self.dtype,
                       attn_impl=self.attn_impl, name=f"block{i}")(x)
        x = nn.LayerNorm(dtype=self.dtype, name="ln_out")(x)
        x = x.mean(axis=1)
        return nn.Dense(self.num_classes, dtype=self.dtype, name="head")(x)


def ViT_S16(**kw) -> ViT:
    """ViT-Small/16 (22M params)."""
    return ViT(patch=16, dim=384, depth=12, n_heads=6, **kw)


def ViT_B16(**kw) -> ViT:
    """ViT-Base/16 (86M params) — the standard benchmark config."""
    return ViT(patch=16, dim=768, depth=12, n_heads=12, **kw)


def ViT_L16(**kw) -> ViT:
    """ViT-Large/16 (307M params)."""
    return ViT(patch=16, dim=1024, depth=24, n_heads=16, **kw)
