"""Transformer layers.

Reference: python/paddle/nn/layer/transformer.py — MultiHeadAttention (:109),
TransformerEncoderLayer (:431), TransformerEncoder (:551),
TransformerDecoderLayer (:623), TransformerDecoder (:768), Transformer (:859).
The attention core is a single fused jnp composition (one XLA fusion group /
flash-attention Pallas kernel under jit) instead of the reference's chain of
matmul/scale/softmax/dropout ops.
"""
from __future__ import annotations

import collections
import numpy as np

import jax

from .layer_base import Layer
from .container import LayerList
from .layers_common import Linear, Dropout, LayerNorm
from . import functional as F
from ..ops import creation, manipulation, math as _math
from ..core.tensor import Tensor


class _CausalMask:
    """Sentinel attn_mask value declaring "standard causal mask" without
    materialising the [L, L] additive tensor. Lets MultiHeadAttention
    route to the fused flash kernel (which applies causality inside the
    kernel) and lets the dense path build the triu mask lazily."""

    def __repr__(self):
        return "<causal attention mask>"


CAUSAL_MASK = _CausalMask()

# measured crossover on the v5e chip (docs/perf_notes.md round 4): XLA
# dense attention wins up to S=2048, the Pallas flash kernel wins 1.39x
# at 4096 and is the only option at 8192 (dense materialises [B,H,S,S])
FLASH_CROSSOVER = 4096


def _convert_attention_mask(attn_mask, dtype):
    """reference: transformer.py _convert_attention_mask — bool mask →
    additive -inf mask."""
    if attn_mask is None:
        return None
    if attn_mask.dtype == np.dtype("bool"):
        return (attn_mask.astype(dtype) - 1.0) * 1e9
    return attn_mask.astype(dtype)


class MultiHeadAttention(Layer):
    """reference: transformer.py:109.

    TPU extension: ``attn_impl`` selects the attention core —
    ``"auto"`` (default) uses the Pallas flash kernel when the sequence
    reaches FLASH_CROSSOVER and the call is eligible (no attention-prob
    dropout in training mode, no need_weights, no incremental cache, and
    the mask is None or the CAUSAL_MASK sentinel), ``"flash"`` forces it
    for any eligible call, ``"dense"`` never uses it. The reference has
    no such knob — its fused attention lives in external libraries."""

    Cache = collections.namedtuple("Cache", ["k", "v"])
    StaticCache = collections.namedtuple("StaticCache", ["k", "v"])

    def __init__(self, embed_dim, num_heads, dropout=0.0, kdim=None, vdim=None,
                 need_weights=False, weight_attr=None, bias_attr=None,
                 attn_impl="auto", attn_blocks=None):
        super().__init__()
        self.embed_dim = embed_dim
        self.kdim = kdim or embed_dim
        self.vdim = vdim or embed_dim
        self.num_heads = num_heads
        self.head_dim = embed_dim // num_heads
        assert self.head_dim * num_heads == embed_dim
        self.dropout = dropout
        self.need_weights = need_weights
        if attn_impl not in ("auto", "dense", "flash"):
            raise ValueError(f"attn_impl {attn_impl!r} not in "
                             "('auto', 'dense', 'flash')")
        self.attn_impl = attn_impl
        # explicit (block_q, block_k) for the flash kernel; None defers to
        # the paddle_tpu.tuner winner cache (falling back to the kernel's
        # historical 128)
        if attn_blocks is not None:
            attn_blocks = (int(attn_blocks[0]), int(attn_blocks[1]))
        self.attn_blocks = attn_blocks
        self.q_proj = Linear(embed_dim, embed_dim, weight_attr, bias_attr)
        self.k_proj = Linear(self.kdim, embed_dim, weight_attr, bias_attr)
        self.v_proj = Linear(self.vdim, embed_dim, weight_attr, bias_attr)
        self.out_proj = Linear(embed_dim, embed_dim, weight_attr, bias_attr)

    def _flash_eligible(self, attn_mask, cache, seq_len):
        if self.attn_impl == "dense":
            return False
        if (self.need_weights or cache is not None
                or (self.dropout and self.training)):
            return False
        if not (attn_mask is None or isinstance(attn_mask, _CausalMask)):
            return False           # arbitrary additive masks: dense only
        if self.head_dim % 8 != 0:
            return False           # lane-tile constraint on the kernel
        if self.attn_impl == "flash":
            return True
        return seq_len >= FLASH_CROSSOVER

    def _split_heads(self, x):
        # [B, L, E] -> [B, H, L, D]
        b, l = x.shape[0], x.shape[1]
        return manipulation.transpose(
            manipulation.reshape(x, [b, l, self.num_heads, self.head_dim]),
            [0, 2, 1, 3])

    def compute_kv(self, key, value):
        return self.StaticCache(self._split_heads(self.k_proj(key)),
                                self._split_heads(self.v_proj(value)))

    def gen_cache(self, key, value=None, type=None):
        if type == MultiHeadAttention.StaticCache:
            return self.compute_kv(key, value if value is not None else key)
        # incremental decoding cache seeded empty
        b = key.shape[0]
        k = creation.zeros([b, self.num_heads, 0, self.head_dim], key.dtype)
        return self.Cache(k, k)

    def forward(self, query, key=None, value=None, attn_mask=None, cache=None):
        key = query if key is None else key
        value = key if value is None else value
        if self._flash_eligible(attn_mask, cache, query.shape[1]):
            # fused Pallas path: [B, L, H, D] layout straight from the
            # projections, causality applied inside the kernel
            from ..ops.pallas_attention import flash_attention
            b, lq = query.shape[0], query.shape[1]
            shape = [b, -1, self.num_heads, self.head_dim]
            with jax.named_scope("gpt/qkv"):
                qf = manipulation.reshape(self.q_proj(query), shape)
                kf = manipulation.reshape(self.k_proj(key), shape)
                vf = manipulation.reshape(self.v_proj(value), shape)
            blocks = self.attn_blocks or (None, None)
            with jax.named_scope("gpt/attn"):
                out, _ = flash_attention(
                    qf, kf, vf, causal=isinstance(attn_mask, _CausalMask),
                    block_q=blocks[0], block_k=blocks[1])
                out = manipulation.reshape(out, [b, lq, self.embed_dim])
            with jax.named_scope("gpt/proj"):
                return self.out_proj(out)
        with jax.named_scope("gpt/qkv"):
            q = self._split_heads(self.q_proj(query))
            if isinstance(cache, self.StaticCache):
                k, v = cache.k, cache.v
            else:
                k = self._split_heads(self.k_proj(key))
                v = self._split_heads(self.v_proj(value))
                if isinstance(cache, self.Cache):
                    k = manipulation.concat([cache.k, k], axis=2)
                    v = manipulation.concat([cache.v, v], axis=2)
                    cache = self.Cache(k, v)
        with jax.named_scope("gpt/attn"):
            out, weights = self._dense_attention(q, k, v, attn_mask)
        with jax.named_scope("gpt/proj"):
            out = self.out_proj(out)

        outs = [out]
        if self.need_weights:
            outs.append(weights)
        if cache is not None and isinstance(cache, self.Cache):
            outs.append(cache)
        return out if len(outs) == 1 else tuple(outs)

    def _dense_attention(self, q, k, v, attn_mask):
        """Softmax attention of ``q`` over ``k``/``v`` ``[B, H, L, D]``
        under ``attn_mask``; returns ``([B, Lq, E], weights)``."""
        if isinstance(attn_mask, _CausalMask):
            # dense fallback for the sentinel: materialise the additive
            # causal mask. With an incremental-decode cache lq < lk and
            # query row i sits at absolute position lk - lq + i, so the
            # triu offset shifts by the prefix length (offset 1 when
            # lq == lk)
            lq, lk = q.shape[2], k.shape[2]
            attn_mask = creation.triu(
                creation.full([lq, lk], -1e9, q.dtype), lk - lq + 1)
        mask = _convert_attention_mask(attn_mask, q.dtype)
        scale = 1.0 / np.sqrt(self.head_dim)
        product = _math.matmul(q * scale, k, transpose_y=True)
        if mask is not None:
            product = product + mask
        weights = F.softmax(product)
        if self.dropout:
            weights = F.dropout(weights, self.dropout, training=self.training,
                                mode="upscale_in_train")
        out = _math.matmul(weights, v)                       # [B,H,L,D]
        out = manipulation.transpose(out, [0, 2, 1, 3])
        return manipulation.reshape(
            out, [out.shape[0], out.shape[1], self.embed_dim]), weights


class TransformerEncoderLayer(Layer):
    """reference: transformer.py:431."""

    def __init__(self, d_model, nhead, dim_feedforward, dropout=0.1,
                 activation="relu", attn_dropout=None, act_dropout=None,
                 normalize_before=False, weight_attr=None, bias_attr=None,
                 attn_impl="auto", attn_blocks=None):
        super().__init__()
        attn_dropout = dropout if attn_dropout is None else attn_dropout
        act_dropout = dropout if act_dropout is None else act_dropout
        self.normalize_before = normalize_before
        self.self_attn = MultiHeadAttention(d_model, nhead, attn_dropout,
                                            weight_attr=weight_attr,
                                            bias_attr=bias_attr,
                                            attn_impl=attn_impl,
                                            attn_blocks=attn_blocks)
        self.linear1 = Linear(d_model, dim_feedforward, weight_attr, bias_attr)
        self.dropout = Dropout(act_dropout, mode="upscale_in_train")
        self.linear2 = Linear(dim_feedforward, d_model, weight_attr, bias_attr)
        self.norm1 = LayerNorm(d_model)
        self.norm2 = LayerNorm(d_model)
        self.dropout1 = Dropout(dropout, mode="upscale_in_train")
        self.dropout2 = Dropout(dropout, mode="upscale_in_train")
        self.activation = getattr(F, activation)

    def forward(self, src, src_mask=None, cache=None):
        # the scopes are the serving block's (models/gpt.py:gpt_block):
        # observability.opscope reads device time by them
        residual = src
        if self.normalize_before:
            with jax.named_scope("gpt/norm"):
                src = self.norm1(src)
        if cache is None:
            src = self.self_attn(src, src, src, src_mask)
        else:
            src, incremental_cache = self.self_attn(src, src, src, src_mask, cache)
        with jax.named_scope("gpt/proj"):
            src = residual + self.dropout1(src)
        if not self.normalize_before:
            with jax.named_scope("gpt/norm"):
                src = self.norm1(src)
        residual = src
        if self.normalize_before:
            with jax.named_scope("gpt/norm"):
                src = self.norm2(src)
        with jax.named_scope("gpt/mlp"):
            src = self.linear2(self.dropout(self.activation(self.linear1(src))))
            src = residual + self.dropout2(src)
        if not self.normalize_before:
            with jax.named_scope("gpt/norm"):
                src = self.norm2(src)
        return src if cache is None else (src, incremental_cache)

    def gen_cache(self, src):
        return self.self_attn.gen_cache(src)


class TransformerEncoder(Layer):
    """reference: transformer.py:551."""

    def __init__(self, encoder_layer, num_layers, norm=None):
        super().__init__()
        import copy
        self.layers = LayerList([encoder_layer] +
                                [copy.deepcopy(encoder_layer)
                                 for _ in range(num_layers - 1)])
        self.num_layers = num_layers
        self.norm = norm

    def forward(self, src, src_mask=None, cache=None):
        output = src
        new_caches = []
        for i, mod in enumerate(self.layers):
            if cache is None:
                output = mod(output, src_mask)
            else:
                output, new_cache = mod(output, src_mask, cache[i])
                new_caches.append(new_cache)
        if self.norm is not None:
            with jax.named_scope("gpt/norm"):
                output = self.norm(output)
        return output if cache is None else (output, new_caches)

    def gen_cache(self, src):
        return [layer.gen_cache(src) for layer in self.layers]


class TransformerDecoderLayer(Layer):
    """reference: transformer.py:623."""

    def __init__(self, d_model, nhead, dim_feedforward, dropout=0.1,
                 activation="relu", attn_dropout=None, act_dropout=None,
                 normalize_before=False, weight_attr=None, bias_attr=None):
        super().__init__()
        attn_dropout = dropout if attn_dropout is None else attn_dropout
        act_dropout = dropout if act_dropout is None else act_dropout
        self.normalize_before = normalize_before
        self.self_attn = MultiHeadAttention(d_model, nhead, attn_dropout,
                                            weight_attr=weight_attr, bias_attr=bias_attr)
        self.cross_attn = MultiHeadAttention(d_model, nhead, attn_dropout,
                                             weight_attr=weight_attr, bias_attr=bias_attr)
        self.linear1 = Linear(d_model, dim_feedforward, weight_attr, bias_attr)
        self.dropout = Dropout(act_dropout, mode="upscale_in_train")
        self.linear2 = Linear(dim_feedforward, d_model, weight_attr, bias_attr)
        self.norm1 = LayerNorm(d_model)
        self.norm2 = LayerNorm(d_model)
        self.norm3 = LayerNorm(d_model)
        self.dropout1 = Dropout(dropout, mode="upscale_in_train")
        self.dropout2 = Dropout(dropout, mode="upscale_in_train")
        self.dropout3 = Dropout(dropout, mode="upscale_in_train")
        self.activation = getattr(F, activation)

    def forward(self, tgt, memory, tgt_mask=None, memory_mask=None, cache=None):
        residual = tgt
        if self.normalize_before:
            tgt = self.norm1(tgt)
        if cache is None:
            tgt = self.self_attn(tgt, tgt, tgt, tgt_mask)
            incremental_cache = None
        else:
            tgt, incremental_cache = self.self_attn(tgt, tgt, tgt, tgt_mask, cache[0])
        tgt = residual + self.dropout1(tgt)
        if not self.normalize_before:
            tgt = self.norm1(tgt)

        residual = tgt
        if self.normalize_before:
            tgt = self.norm2(tgt)
        if cache is None:
            tgt = self.cross_attn(tgt, memory, memory, memory_mask)
        else:
            tgt = self.cross_attn(tgt, memory, memory, memory_mask, cache[1])
        tgt = residual + self.dropout2(tgt)
        if not self.normalize_before:
            tgt = self.norm2(tgt)

        residual = tgt
        if self.normalize_before:
            tgt = self.norm3(tgt)
        tgt = self.linear2(self.dropout(self.activation(self.linear1(tgt))))
        tgt = residual + self.dropout3(tgt)
        if not self.normalize_before:
            tgt = self.norm3(tgt)
        return tgt if cache is None else (tgt, (incremental_cache,))

    def gen_cache(self, memory):
        incremental = self.self_attn.gen_cache(memory)
        static = self.cross_attn.gen_cache(memory, memory,
                                           type=MultiHeadAttention.StaticCache)
        return incremental, static


class TransformerDecoder(Layer):
    """reference: transformer.py:768."""

    def __init__(self, decoder_layer, num_layers, norm=None):
        super().__init__()
        import copy
        self.layers = LayerList([decoder_layer] +
                                [copy.deepcopy(decoder_layer)
                                 for _ in range(num_layers - 1)])
        self.num_layers = num_layers
        self.norm = norm

    def forward(self, tgt, memory, tgt_mask=None, memory_mask=None, cache=None):
        output = tgt
        new_caches = []
        for i, mod in enumerate(self.layers):
            if cache is None:
                output = mod(output, memory, tgt_mask, memory_mask)
            else:
                output, new_cache = mod(output, memory, tgt_mask, memory_mask,
                                        cache[i])
                new_caches.append(new_cache)
        if self.norm is not None:
            output = self.norm(output)
        return output if cache is None else (output, new_caches)

    def gen_cache(self, memory, do_zip=False):
        return [layer.gen_cache(memory) for layer in self.layers]


class Transformer(Layer):
    """reference: transformer.py:859."""

    def __init__(self, d_model=512, nhead=8, num_encoder_layers=6,
                 num_decoder_layers=6, dim_feedforward=2048, dropout=0.1,
                 activation="relu", attn_dropout=None, act_dropout=None,
                 normalize_before=False, weight_attr=None, bias_attr=None,
                 custom_encoder=None, custom_decoder=None):
        super().__init__()
        if custom_encoder is not None:
            self.encoder = custom_encoder
        else:
            enc_layer = TransformerEncoderLayer(
                d_model, nhead, dim_feedforward, dropout, activation,
                attn_dropout, act_dropout, normalize_before, weight_attr, bias_attr)
            enc_norm = LayerNorm(d_model) if normalize_before else None
            self.encoder = TransformerEncoder(enc_layer, num_encoder_layers, enc_norm)
        if custom_decoder is not None:
            self.decoder = custom_decoder
        else:
            dec_layer = TransformerDecoderLayer(
                d_model, nhead, dim_feedforward, dropout, activation,
                attn_dropout, act_dropout, normalize_before, weight_attr, bias_attr)
            dec_norm = LayerNorm(d_model) if normalize_before else None
            self.decoder = TransformerDecoder(dec_layer, num_decoder_layers, dec_norm)
        self.d_model = d_model
        self.nhead = nhead

    def forward(self, src, tgt, src_mask=None, tgt_mask=None, memory_mask=None):
        memory = self.encoder(src, src_mask)
        return self.decoder(tgt, memory, tgt_mask, memory_mask)

    def generate_square_subsequent_mask(self, length):
        return creation.triu(
            creation.full([length, length], -np.inf, "float32"), 1)
