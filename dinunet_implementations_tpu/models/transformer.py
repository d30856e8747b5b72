"""Multimodal FS+ICA transformer classifier.

TPU-build extension (BASELINE.json configs: "Multimodal FS+ICA Transformer,
64-site DP-SGD on v4-128"). Fuses the two reference modalities into one token
sequence:

- FS branch: the 66 aseg volumes → one token;
- ICA branch: each temporal window (``num_components × window_size``) → one
  token (same windowing semantics as the ICA dataset, data/ica.py);
- a learned CLS token is prepended; learned positional embeddings; pre-LN
  transformer blocks; the CLS state feeds the classifier head.

Attention is a custom q/k/v implementation (not ``nn.SelfAttention``) so the
sequence-parallel ring variant (parallel/sequence.py) can swap in for long
sequences: set ``attention="ring"`` with a bound mesh ``model`` axis.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

from .layers import compute_dtype_of, dense


def dot_product_attention(q, k, v):
    """[B, T, N, Hd] q/k/v → [B, T, N, Hd]; plain softmax attention.
    Logits accumulate and softmax runs in f32 regardless of input dtype
    (bf16 q/k/v under mixed precision); output returns at v's dtype."""
    scale = q.shape[-1] ** -0.5
    logits = jnp.einsum(
        "btnh,bsnh->bnts", q, k, preferred_element_type=jnp.float32
    ) * scale
    weights = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("bnts,bsnh->btnh", weights, v)


class MultiHeadAttention(nn.Module):
    embed_dim: int
    num_heads: int
    attention: str = "local"  # "local" | "ring" (sequence-parallel)
    axis_name: str | None = None  # mesh axis for ring attention
    compute_dtype: str | None = None  # bf16 matmuls, f32 softmax/accum

    @nn.compact
    def __call__(self, x):
        B, T, E = x.shape
        N = self.num_heads
        Hd = E // N
        cdt = compute_dtype_of(self.compute_dtype)
        qkv = dense(3 * E, fan_in=E, name="qkv", dtype=cdt)(x).reshape(
            B, T, 3, N, Hd
        )
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        if self.attention == "ring":
            from ..parallel.sequence import ring_attention

            out = ring_attention(q, k, v, axis_name=self.axis_name)
        else:
            out = dot_product_attention(q, k, v)
        return dense(E, fan_in=E, name="proj", dtype=cdt)(out.reshape(B, T, E))


class TransformerBlock(nn.Module):
    embed_dim: int
    num_heads: int
    mlp_ratio: int = 4
    dropout_rate: float = 0.1
    attention: str = "local"
    axis_name: str | None = None
    compute_dtype: str | None = None  # bf16 matmuls; LayerNorm/residual f32

    def _dropout(self, h, train: bool):
        if not train or self.dropout_rate == 0.0:
            return h
        if self.attention == "ring" and self.axis_name is not None:
            # h is this device's token chunk; the dropout rng is replicated
            # across the model axis, so plain nn.Dropout would draw the SAME
            # mask for every chunk (correlated dropout, tiled over the token
            # axis). Fold the axis index in so each chunk gets its own mask.
            rng = jax.random.fold_in(
                self.make_rng("dropout"), jax.lax.axis_index(self.axis_name)
            )
            keep = 1.0 - self.dropout_rate
            mask = jax.random.bernoulli(rng, keep, h.shape)
            return jnp.where(mask, h / keep, jnp.zeros_like(h))
        return nn.Dropout(self.dropout_rate, deterministic=False)(h)

    @nn.compact
    def __call__(self, x, train: bool = True):
        cdt = compute_dtype_of(self.compute_dtype)
        h = nn.LayerNorm(name="ln1")(x)  # LN stats at f32 (x is f32 stream)
        h = MultiHeadAttention(
            self.embed_dim, self.num_heads, self.attention, self.axis_name,
            self.compute_dtype, name="attn",
        )(h)
        # residual stream stays f32 (f32 + bf16 promotes to f32)
        x = x + self._dropout(h.astype(jnp.float32), train)
        h = nn.LayerNorm(name="ln2")(x)
        h = dense(self.embed_dim * self.mlp_ratio, fan_in=self.embed_dim,
                  name="mlp1", dtype=cdt)(h)
        h = nn.gelu(h)
        h = dense(self.embed_dim, fan_in=self.embed_dim * self.mlp_ratio,
                  name="mlp2", dtype=cdt)(h)
        return x + self._dropout(h.astype(jnp.float32), train)


class MultimodalNet(nn.Module):
    fs_input_size: int = 66
    num_comps: int = 100
    window_size: int = 10
    embed_dim: int = 256
    num_heads: int = 8
    num_layers: int = 4
    mlp_ratio: int = 4
    num_cls: int = 2
    dropout_rate: float = 0.1
    attention: str = "local"
    axis_name: str | None = None
    # "bfloat16" runs every matmul (embeddings, qkv/proj, MLPs) in bf16 with
    # f32 softmax/LayerNorm/residual stream; None = full f32
    compute_dtype: str | None = None

    @nn.compact
    def __call__(self, x, train: bool = True, mask=None):
        """``x``: packed ``[B, fs_input_size + S*num_comps*window_size]``
        (data/multimodal.py packs both modalities into one flat vector so the
        standard site-batch pipeline applies); unpacked here."""
        B = x.shape[0]
        fs = x[:, : self.fs_input_size]
        ica = x[:, self.fs_input_size :].reshape(
            B, -1, self.num_comps * self.window_size
        )  # [B, S, C*W]

        cdt = compute_dtype_of(self.compute_dtype)
        fs_tok = dense(self.embed_dim, fan_in=self.fs_input_size,
                       name="fs_embed", dtype=cdt)(fs)
        ica_tok = dense(
            self.embed_dim, fan_in=self.num_comps * self.window_size,
            name="ica_embed", dtype=cdt,
        )(ica)
        cls = self.param(
            "cls", nn.initializers.normal(0.02), (1, 1, self.embed_dim)
        )
        tokens = jnp.concatenate(
            [jnp.tile(cls, (B, 1, 1)),
             fs_tok[:, None, :].astype(jnp.float32),
             ica_tok.astype(jnp.float32)], axis=1
        )  # token/residual stream is f32; block matmuls re-cast internally
        T = tokens.shape[1]
        pos = self.param(
            "pos_embed", nn.initializers.normal(0.02), (1, T, self.embed_dim)
        )
        h = tokens + pos
        ring = self.attention == "ring" and self.axis_name is not None
        if ring:
            # sequence parallelism: shard the token axis over the mesh axis —
            # each device keeps its chunk through every block (attention is
            # the only cross-chunk op, handled by ring_attention's K/V ring)
            from ..parallel.sequence import gather_sequence, shard_sequence

            n = jax.lax.axis_size(self.axis_name)
            if T % n:
                raise ValueError(
                    f"ring attention needs tokens ({T}) divisible by the "
                    f"{self.axis_name!r} axis size ({n})"
                )
            h = shard_sequence(h, self.axis_name, axis=1)
        for i in range(self.num_layers):
            h = TransformerBlock(
                self.embed_dim, self.num_heads, self.mlp_ratio, self.dropout_rate,
                self.attention, self.axis_name, self.compute_dtype,
                name=f"block_{i}",
            )(h, train=train)
        h = nn.LayerNorm(name="ln_f")(h)
        if ring:
            # the CLS token lives in chunk 0; gather so every device returns
            # identical logits (all_gather transposes to reduce-scatter — AD
            # routes the CLS cotangent back to the owning chunk)
            h = gather_sequence(h, self.axis_name, axis=1)
        return dense(self.num_cls, fan_in=self.embed_dim, name="head")(h[:, 0])
