"""Decoder-only LM: dense or MoE FFN, GQA + RoPE, optional sliding window.

Weights for all layers are stacked on a leading [L] axis and the blocks run
under jax.lax.scan (+ optional jax.checkpoint for remat), so HLO size and
compile time are depth-independent — required for the 64/94-layer dry-runs.

Three entry points per the assigned shapes:
  forward / loss_fn       — training (train_4k)
  prefill                 — inference prefill, returns logits + KV cache
  decode_step             — one token against a KV cache (decode_32k/long_500k)
"""
from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial

import jax
import jax.numpy as jnp

from repro.models.layers import (apply_rope, chunked_attention, decode_attention,
                                 dense_init, embed_init, rms_norm)
from repro.models.moe import MoEConfig, moe_apply, moe_capacity, moe_init


@dataclass(frozen=True)
class TransformerConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    d_head: int | None = None
    qkv_bias: bool = False
    sliding_window: int | None = None
    rope_theta: float = 1_000_000.0
    moe: MoEConfig | None = None          # if set, d_ff is per-expert width
    compute_dtype: str = "bfloat16"
    param_dtype: str = "float32"
    remat: bool = True
    q_chunk: int = 512
    k_chunk: int = 1024
    # probe mode (launch/dryrun.py cost extrapolation): unroll every scan so
    # XLA's count-loop-bodies-once cost analysis sees the true op counts
    scan_unroll: bool = False
    # Megatron-style sequence parallelism for the residual stream at layer
    # boundaries: the remat-saved per-layer carry is sharded over the model
    # axis on the sequence dim (16x less HBM for saved activations, at the
    # cost of a per-layer all-gather)
    seq_shard_carry: bool = True

    @property
    def head_dim(self) -> int:
        return self.d_head or self.d_model // self.n_heads

    @property
    def vocab_padded(self) -> int:
        """Embedding/head rows padded to 256 so vocab sharding tiles any
        mesh axis (Megatron padded-vocab convention). Logits over padding
        rows exist but no data pipeline ever emits those ids."""
        return ((self.vocab + 255) // 256) * 256

    @property
    def cdtype(self):
        return jnp.dtype(self.compute_dtype)

    @property
    def pdtype(self):
        return jnp.dtype(self.param_dtype)

    def n_params(self) -> int:
        """Total parameter count (embeddings included)."""
        d, hd = self.d_model, self.head_dim
        attn = d * hd * (self.n_heads + 2 * self.n_kv_heads) + self.n_heads * hd * d
        if self.moe:
            ffn = self.moe.n_experts * 3 * d * self.d_ff + d * self.moe.n_experts
        else:
            ffn = 3 * d * self.d_ff
        per_layer = attn + ffn + 2 * d
        return self.n_layers * per_layer + 2 * self.vocab * d + d

    def n_active_params(self) -> int:
        """Params touched per token (MoE: top_k experts only)."""
        if not self.moe:
            return self.n_params()
        d = self.d_model
        attn = d * self.head_dim * (self.n_heads + 2 * self.n_kv_heads) \
            + self.n_heads * self.head_dim * d
        ffn = self.moe.top_k * 3 * d * self.d_ff + d * self.moe.n_experts
        return self.n_layers * (attn + ffn + 2 * d) + 2 * self.vocab * d + d


# ------------------------------------------------------------------ init ---

def init_params(key, cfg: TransformerConfig):
    d, hd, h, hkv = cfg.d_model, cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    l = cfg.n_layers
    keys = jax.random.split(key, 12)
    dt = cfg.pdtype

    def stack(initfn, k, *shape_args):
        ks = jax.random.split(k, l)
        return jnp.stack([initfn(kk, *shape_args) for kk in ks])

    layer = {
        "ln1": jnp.ones((l, d), dt),
        "ln2": jnp.ones((l, d), dt),
        "wq": stack(lambda k: dense_init(k, d, h * hd, dt), keys[0]),
        "wk": stack(lambda k: dense_init(k, d, hkv * hd, dt), keys[1]),
        "wv": stack(lambda k: dense_init(k, d, hkv * hd, dt), keys[2]),
        "wo": stack(lambda k: dense_init(k, h * hd, d, dt), keys[3]),
    }
    if cfg.qkv_bias:
        layer["bq"] = jnp.zeros((l, h * hd), dt)
        layer["bk"] = jnp.zeros((l, hkv * hd), dt)
        layer["bv"] = jnp.zeros((l, hkv * hd), dt)
    if cfg.moe:
        moe_ks = jax.random.split(keys[4], l)
        moes = [moe_init(k, d, cfg.d_ff, cfg.moe, dt) for k in moe_ks]
        layer["moe"] = jax.tree.map(lambda *xs: jnp.stack(xs), *moes)
    else:
        layer["w1"] = stack(lambda k: dense_init(k, d, cfg.d_ff, dt), keys[5])
        layer["w3"] = stack(lambda k: dense_init(k, d, cfg.d_ff, dt), keys[6])
        layer["w2"] = stack(lambda k: dense_init(k, cfg.d_ff, d, dt), keys[7])
    return {
        "embed": embed_init(keys[8], cfg.vocab_padded, d, dt),
        "layers": layer,
        "final_ln": jnp.ones((d,), dt),
        "lm_head": dense_init(keys[9], d, cfg.vocab_padded, dt),
    }


# ----------------------------------------------------------------- blocks --

def _attn(lp, x, cfg: TransformerConfig, positions, kv=None, cache_len=None):
    """x: [B, S, D]. kv: optional (k_cache, v_cache) for decode."""
    b, s, d = x.shape
    h, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = x @ lp["wq"]
    k = x @ lp["wk"]
    v = x @ lp["wv"]
    if cfg.qkv_bias:
        q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
    q = q.reshape(b, s, h, hd)
    k = k.reshape(b, s, hkv, hd)
    v = v.reshape(b, s, hkv, hd)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    if kv is None:
        out = chunked_attention(q, k, v, causal=True,
                                window=cfg.sliding_window,
                                q_chunk=cfg.q_chunk, k_chunk=cfg.k_chunk,
                                unroll=cfg.scan_unroll)
        new_kv = (k, v)
    else:
        k_cache, v_cache = kv
        k_cache = jax.lax.dynamic_update_slice_in_dim(k_cache, k, cache_len, 1)
        v_cache = jax.lax.dynamic_update_slice_in_dim(v_cache, v, cache_len, 1)
        out = decode_attention(q, k_cache, v_cache, cache_len + s,
                               window=cfg.sliding_window)
        new_kv = (k_cache, v_cache)
    out = out.reshape(b, s, h * hd)
    return out @ lp["wo"], new_kv


def _ffn(lp, x, cfg: TransformerConfig):
    if cfg.moe:
        b, s, d = x.shape
        if cfg.moe.impl == "a2a":
            y, aux = _moe_a2a_sharded(lp["moe"], x, cfg)
            if y is not None:
                return y, aux
        y, aux = moe_apply(lp["moe"], x.reshape(b * s, d), cfg.moe)
        return y.reshape(b, s, d), aux
    h = jax.nn.silu(x @ lp["w1"]) * (x @ lp["w3"])
    return h @ lp["w2"], jnp.float32(0.0)


def _moe_a2a_sharded(mp, x, cfg: TransformerConfig):
    """shard_map wrapper for the all-to-all MoE (§Perf iteration B).
    Returns (None, None) when no suitable mesh is active (smoke tests /
    expert count not tiling the model axis) so the caller falls back."""
    from jax.sharding import PartitionSpec as P
    from repro.distributed.sharding import _current_mesh
    from repro.models.moe import moe_apply_a2a
    mesh = _current_mesh()
    if mesh is None or "model" not in mesh.axis_names:
        return None, None
    sizes = dict(zip(mesh.axis_names, mesh.axis_sizes))
    ep = sizes["model"]
    if cfg.moe.n_experts % ep:
        return None, None
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    b, s, d = x.shape
    dp_size = 1
    for a in dp:
        dp_size *= sizes[a]
    xspec = P(dp if (dp and b % dp_size == 0) else None, None, None)

    def fn(xl, router, w1, w3, w2):
        bl, sl, dl = xl.shape
        params = {"router": router, "w1": w1, "w3": w3, "w2": w2}
        y, aux = moe_apply_a2a(params, xl.reshape(bl * sl, dl), cfg.moe,
                               ep=ep, axis_name="model")
        axes = dp + ("model",)
        aux = jax.lax.pmean(aux, axes) if dp else jax.lax.pmean(aux, "model")
        return y.reshape(bl, sl, dl), aux

    y, aux = jax.shard_map(
        fn, mesh=mesh,
        in_specs=(xspec, P(None, None), P("model", None, None),
                  P("model", None, None), P("model", None, None)),
        out_specs=(xspec, P()), check_vma=False,
    )(x, mp["router"], mp["w1"], mp["w3"], mp["w2"])
    return y, aux


def _block(lp, x, cfg: TransformerConfig, positions, kv=None, cache_len=None):
    from repro.distributed.sharding import shard_activation
    x = shard_activation(x, "batch", None, None)   # residual: batch over data
    a, new_kv = _attn(lp, rms_norm(x, lp["ln1"]), cfg, positions, kv, cache_len)
    x = x + a
    f, aux = _ffn(lp, rms_norm(x, lp["ln2"]), cfg)
    x = x + f
    if cfg.seq_shard_carry and kv is None:
        # saved-for-backward carry lives sequence-sharded (Megatron SP)
        x = shard_activation(x, "batch", "tp", None)
    return x, aux, new_kv


# --------------------------------------------------------------- forward ---

def _cast(params, dtype):
    return jax.tree.map(lambda a: a.astype(dtype)
                        if a.dtype in (jnp.float32, jnp.bfloat16) else a, params)


def forward(params, tokens: jax.Array, cfg: TransformerConfig,
            return_kv: bool = False, start_pos: int = 0):
    """tokens: [B, S] -> logits [B, S, V] (f32). Optionally the KV cache."""
    b, s = tokens.shape
    cp = _cast(params, cfg.cdtype)
    x = cp["embed"][tokens]
    positions = start_pos + jnp.arange(s)[None, :]

    def body(carry, lp):
        x, aux = carry
        x, aux_l, kv = _block(lp, x, cfg, positions)
        return (x, aux + aux_l), kv if return_kv else 0.0

    body_fn = jax.checkpoint(body) if cfg.remat else body
    (x, aux), kvs = jax.lax.scan(body_fn, (x, jnp.float32(0.0)), cp["layers"],
                                 unroll=cfg.n_layers if cfg.scan_unroll else 1)
    x = rms_norm(x, cp["final_ln"])
    logits = (x @ cp["lm_head"]).astype(jnp.float32)
    return (logits, aux, kvs) if return_kv else (logits, aux)


def loss_fn(params, batch, cfg: TransformerConfig, aux_weight: float = 0.01):
    """batch: {'tokens': [B, S+1]} -> scalar mean xent + moe aux."""
    tokens = batch["tokens"][:, :-1]
    labels = batch["tokens"][:, 1:]
    logits, aux = forward(params, tokens, cfg)
    logz = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    xent = jnp.mean(logz - ll)
    return xent + aux_weight * aux / cfg.n_layers


def prefill(params, tokens: jax.Array, cfg: TransformerConfig,
            pad_to: int | None = None):
    """Returns (last-token logits [B, V], kv cache [L, B, S_pad, Hkv, Dh] x2)."""
    logits, _, kvs = forward(params, tokens, cfg, return_kv=True)
    k_cache, v_cache = kvs
    if pad_to:
        pad = pad_to - k_cache.shape[2]
        k_cache = jnp.pad(k_cache, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
    return logits[:, -1], (k_cache, v_cache)


def decode_step(params, token: jax.Array, kv_cache, cache_len,
                cfg: TransformerConfig):
    """token: [B, 1]; kv_cache: (k, v) each [L, B, S, Hkv, Dh];
    cache_len: int32 scalar — number of valid positions.
    Returns (logits [B, V], updated kv_cache)."""
    cp = _cast(params, cfg.cdtype)
    x = cp["embed"][token]
    positions = jnp.full((token.shape[0], 1), cache_len, jnp.int32)

    def body(carry, inputs):
        x, = carry
        lp, kv = inputs
        x, _, new_kv = _block(lp, x, cfg, positions, kv=kv, cache_len=cache_len)
        return (x,), new_kv

    (x,), new_kvs = jax.lax.scan(body, (x,), (cp["layers"], kv_cache),
                                 unroll=cfg.n_layers if cfg.scan_unroll else 1)
    x = rms_norm(x, cp["final_ln"])
    logits = (x[:, 0] @ cp["lm_head"]).astype(jnp.float32)
    return logits, new_kvs


def make_kv_cache(cfg: TransformerConfig, batch: int, max_len: int,
                  dtype=None):
    dt = dtype or cfg.cdtype
    shape = (cfg.n_layers, batch, max_len, cfg.n_kv_heads, cfg.head_dim)
    return (jnp.zeros(shape, dt), jnp.zeros(shape, dt))
