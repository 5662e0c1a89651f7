"""Layer library for the ten assigned architectures — pure JAX.

Blocks: RMSNorm, RoPE/M-RoPE, GQA attention (blockwise-causal for train and
prefill, cached for decode, optional sliding window / qk-norm / QKV bias),
SwiGLU/GeGLU/vanilla FFN, sort-based expert-parallel MoE, Mamba-2 SSD
(chunked, MXU-friendly matmuls), RG-LRU (associative scan), causal depthwise
conv.  All arrays are annotated with logical axes (``lsc``) so the same code
lowers for every mesh in the dry-run matrix.

Dtype discipline: parameters are stored f32 (master copy), compute runs in
``cfg.compute_dtype`` (bf16 on TPU), and numerically sensitive reductions
(softmax, norms, SSM/LRU states, losses) stay f32.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
from jax import shard_map
from jax import lax

from repro.distributed.partitioning import (
    current_mesh_shape,
    current_rules,
    logical_spec,
    lsc,
)

Params = dict
F32 = jnp.float32

MASK_VALUE = -1e30


def normal(key, shape, scale, dtype=jnp.float32):
    return scale * jax.random.normal(key, shape, dtype)


def cast(x, dtype):
    """Cast a weight to the compute dtype; dequantizes int8 weight records.

    A quantized weight is the pytree leaf-pair ``{"q": int8 (in, out),
    "s": scale (1, out)}`` (per-output-channel absmax).  The dequant
    multiply fuses into the consuming matmul on TPU, so the HBM read is the
    int8 buffer — the serving path's §Perf iteration 5.
    """
    if isinstance(x, dict) and "q" in x:
        return x["q"].astype(dtype) * x["s"].astype(dtype)
    return x.astype(dtype) if x.dtype != dtype else x


def quantize_weight(w: jax.Array) -> dict:
    """Per-output-channel absmax int8 quantization of a 2D weight."""
    s = jnp.max(jnp.abs(w), axis=0, keepdims=True) / 127.0
    s = jnp.maximum(s, 1e-12)
    q = jnp.clip(jnp.round(w / s), -127, 127).astype(jnp.int8)
    return {"q": q, "s": s.astype(jnp.bfloat16)}


# RG-LRU gate matrices (w_a, w_x) stay bf16: they parameterize decay rates,
# where int8 grid error compounds over thousands of recurrence steps
_QUANT_NAMES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "w_in",
                "w_out", "w_branch", "w_zx")


def quantize_for_serving(params: Params) -> Params:
    """int8-quantize the large 2D matmul weights for the decode path.

    Embeddings / lm_head / norms / small vectors stay bf16-castable.  The
    quantized tree is TP-only shardable (no FSDP axis needed): a 110B model
    holds 6.9 GB int8 per device at TP=16 — weight all-gathers disappear
    from the decode step.
    """
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    out = []
    for kp, leaf in flat:
        name = None
        for p in kp:
            if hasattr(p, "key"):
                name = p.key
        if (
            name in _QUANT_NAMES
            and hasattr(leaf, "ndim")
            and leaf.ndim >= 2
            and jnp.issubdtype(leaf.dtype, jnp.floating)
        ):
            if leaf.ndim == 2:
                out.append(quantize_weight(leaf))
            else:  # stacked unit weights (n_units, in, out): vmap the quant
                out.append(jax.vmap(quantize_weight)(leaf))
        elif (
            name not in ("a_log", "dt_bias", "lambda_", "d_skip")  # stay f32
            and hasattr(leaf, "dtype")
            and jnp.issubdtype(leaf.dtype, jnp.floating)
        ):
            out.append(leaf.astype(jnp.bfloat16))
        else:
            out.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, out)


# ------------------------------------------------------------------ norms
def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    xf = x.astype(F32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(F32))
    return out.astype(dtype)


def init_rms_norm(d: int) -> Params:
    return {"scale": jnp.zeros((d,), F32)}  # stored as (1 + scale), gemma-style


# ------------------------------------------------------------------- RoPE
def mrope_sections(head_dim: int) -> tuple[int, int, int]:
    """Frequency-index split for M-RoPE (temporal, height, width).

    Matches Qwen2-VL's published 16/24/24 split at head_dim=128 and scales
    proportionally elsewhere: s0 = hd/8, s1 = s2 = (hd/2 - s0)/2.
    """
    half = head_dim // 2
    s0 = head_dim // 8
    s1 = (half - s0) // 2
    return (s0, s1, half - s0 - s1)


def rope_cos_sin(
    positions: jax.Array, head_dim: int, theta: float, mrope: bool = False
) -> tuple[jax.Array, jax.Array]:
    """cos/sin tables: positions (B,S) → (B,S,half); (B,3,S) for M-RoPE."""
    half = head_dim // 2
    freqs = theta ** (-jnp.arange(half, dtype=F32) / half)  # (half,)
    if not mrope:
        ang = positions.astype(F32)[..., None] * freqs  # (B,S,half)
    else:
        if positions.ndim != 3:
            raise ValueError("M-RoPE wants positions (B, 3, S)")
        ang3 = positions.astype(F32)[..., None] * freqs  # (B,3,S,half)
        sec = mrope_sections(head_dim)
        comp = jnp.concatenate(
            [jnp.full((n,), i, jnp.int32) for i, n in enumerate(sec)]
        )  # (half,) -> which of t/h/w drives each frequency
        onehot = jax.nn.one_hot(comp, 3, dtype=F32)  # (half, 3)
        ang = jnp.einsum("bcsf,fc->bsf", ang3, onehot)  # pick component per freq
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x (B,S,H,Dh) rotated with (B,S,half) tables (llama-style half split)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c = cos[:, :, None, :].astype(F32)
    s = sin[:, :, None, :].astype(F32)
    x1f, x2f = x1.astype(F32), x2.astype(F32)
    out = jnp.concatenate([x1f * c - x2f * s, x1f * s + x2f * c], axis=-1)
    return out.astype(x.dtype)


# -------------------------------------------------------------- attention
@dataclasses.dataclass(frozen=True)
class AttnSpec:
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    qkv_bias: bool = False
    qk_norm: bool = False
    rope_theta: float = 1e4
    mrope: bool = False
    window: int | None = None  # None = full causal
    causal: bool = True  # False: bidirectional (encoder self-attention)
    softmax_scale: float | None = None

    @property
    def scale(self) -> float:
        return self.softmax_scale or self.head_dim**-0.5


def init_attention(key, spec: AttnSpec) -> Params:
    d, h, k, hd = spec.d_model, spec.n_heads, spec.n_kv_heads, spec.head_dim
    ks = jax.random.split(key, 4)
    s = d**-0.5
    p = {
        "wq": normal(ks[0], (d, h * hd), s),
        "wk": normal(ks[1], (d, k * hd), s),
        "wv": normal(ks[2], (d, k * hd), s),
        "wo": normal(ks[3], (h * hd, d), (h * hd) ** -0.5),
    }
    if spec.qkv_bias:
        p["bq"] = jnp.zeros((h * hd,), F32)
        p["bk"] = jnp.zeros((k * hd,), F32)
        p["bv"] = jnp.zeros((k * hd,), F32)
    if spec.qk_norm:
        p["q_norm"] = init_rms_norm(hd)
        p["k_norm"] = init_rms_norm(hd)
    return p


def _qkv(params: Params, spec: AttnSpec, x: jax.Array, cos, sin):
    """Project + rope; returns q (B,S,H,Dh), k/v (B,S,K,Dh)."""
    b, s, _ = x.shape
    dt = x.dtype
    q = x @ cast(params["wq"], dt)
    k = x @ cast(params["wk"], dt)
    v = x @ cast(params["wv"], dt)
    if spec.qkv_bias:
        q = q + cast(params["bq"], dt)
        k = k + cast(params["bk"], dt)
        v = v + cast(params["bv"], dt)
    q = q.reshape(b, s, spec.n_heads, spec.head_dim)
    k = k.reshape(b, s, spec.n_kv_heads, spec.head_dim)
    v = v.reshape(b, s, spec.n_kv_heads, spec.head_dim)
    q = lsc(q, "batch", None, "heads", None)
    k = lsc(k, "batch", None, "kv_heads", None)
    v = lsc(v, "batch", None, "kv_heads", None)
    if spec.qk_norm:
        q = rms_norm(q, params["q_norm"]["scale"])
        k = rms_norm(k, params["k_norm"]["scale"])
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    return q, k, v


def blockwise_attention(
    q: jax.Array,  # (B, S, H, Dh)
    k: jax.Array,  # (B, S, K, Dh)
    v: jax.Array,
    spec: AttnSpec,
    chunk: int = 1024,
) -> jax.Array:
    """Flash-style causal attention: online-softmax scan over KV chunks.

    Peak memory is O(S * chunk) logits instead of O(S^2); the paper-side
    analogue is the RME never shipping more than a reorg-buffer's worth of
    data at a time.  The ``window`` in ``spec`` applies a sliding-window mask
    (gemma3 local layers, recurrentgemma local attention).
    """
    b, s, h, hd = q.shape
    kh = k.shape[2]
    g = h // kh  # GQA group size
    chunk = min(chunk, s)
    pad = (-s) % chunk
    if pad:  # pad KV to a chunk multiple; padded keys are masked out below
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    s_kv = s + pad
    n_kv = s_kv // chunk
    window = spec.window or s_kv

    # keep operands in compute dtype (bf16): collectives and HBM traffic at
    # half width; the MXU accumulates in f32 via preferred_element_type
    qh = (q * spec.scale).reshape(b, s, kh, g, hd)
    q_pos = jnp.arange(s)

    def step(carry, inputs):
        acc, m, l = carry
        kc, vc, kv_start = inputs  # (B, chunk, K, Dh) ×2, scalar
        k_pos = kv_start + jnp.arange(chunk)
        logits = jnp.einsum(
            "bqkgd,bckd->bqkgc", qh, kc, preferred_element_type=F32
        )
        dist = q_pos[:, None] - k_pos[None, :]
        if spec.causal:
            mask = (dist >= 0) & (dist < window)  # (S, chunk)
        else:
            mask = jnp.abs(dist) < window  # bidirectional (encoder)
        mask = mask & (k_pos < s)[None, :]  # drop chunk padding
        logits = jnp.where(mask[None, :, None, None, :], logits, MASK_VALUE)
        m_new = jnp.maximum(m, logits.max(axis=-1))
        p = jnp.exp(logits - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(axis=-1)
        pv = jnp.einsum(
            "bqkgc,bckd->bqkgd", p.astype(q.dtype), vc,
            preferred_element_type=F32,
        )
        acc_new = acc * alpha[..., None] + pv
        return (acc_new, m_new, l_new), None

    acc0 = jnp.zeros((b, s, kh, g, hd), F32)
    m0 = jnp.full((b, s, kh, g), -jnp.inf, F32)
    l0 = jnp.zeros((b, s, kh, g), F32)
    kc = k.reshape(b, n_kv, chunk, kh, hd).swapaxes(0, 1)
    vc = v.reshape(b, n_kv, chunk, kh, hd).swapaxes(0, 1)
    del k, v
    starts = jnp.arange(n_kv) * chunk
    # checkpoint the chunk step: its backward recomputes the (S × chunk)
    # probability tile instead of the scan stashing one per chunk — the
    # flash-attention recompute schedule, expressed at the XLA level
    # (§Perf iteration 7; crucial where heads can't shard, e.g. 40 heads
    # on a 16-way model axis)
    (acc, m, l), _ = lax.scan(
        jax.checkpoint(step), (acc0, m0, l0), (kc, vc, starts)
    )
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.reshape(b, s, h, hd).astype(q.dtype)


def _attend(q, k, v, spec: AttnSpec, chunk: int) -> jax.Array:
    """Attention dispatch: fused Pallas kernel on TPU, XLA blockwise else.

    The kernel keeps logits in VMEM (§Perf iteration 6); the XLA path is the
    oracle-checked fallback used on CPU (tests, dry-run lowering).
    """
    if jax.default_backend() == "tpu":  # pragma: no cover - TPU runtime only
        from repro.kernels.flash_attention import flash_attention

        return flash_attention(
            q, k, v, causal=spec.causal, window=spec.window, interpret=False
        )
    return blockwise_attention(q, k, v, spec, chunk=chunk)


def attention_train(
    params: Params, spec: AttnSpec, x: jax.Array, positions: jax.Array,
    chunk: int = 1024,
) -> jax.Array:
    cos, sin = rope_cos_sin(positions, spec.head_dim, spec.rope_theta, spec.mrope)
    q, k, v = _qkv(params, spec, x, cos, sin)
    out = _attend(q, k, v, spec, chunk=chunk)
    out = lsc(out, "batch", None, "heads", None)
    b, s = x.shape[:2]
    out = out.reshape(b, s, spec.n_heads * spec.head_dim)
    return lsc(out @ cast(params["wo"], x.dtype), "batch", None, None)


def attention_prefill(
    params: Params, spec: AttnSpec, x: jax.Array, positions: jax.Array,
    cache_len: int, chunk: int = 1024,
) -> tuple[jax.Array, dict]:
    """Like train, but also emits the KV cache laid out for decode.

    Cache layout: (B, K, cache_len, Dh) with the *sequence* dim annotated
    ``kv_seq`` — sharded over the model axis at serve time (decode-SP), the
    cluster analogue of the RME assembling a line from parallel banks.
    """
    cos, sin = rope_cos_sin(positions, spec.head_dim, spec.rope_theta, spec.mrope)
    q, k, v = _qkv(params, spec, x, cos, sin)
    out = _attend(q, k, v, spec, chunk=chunk)
    b, s = x.shape[:2]
    y = out.reshape(b, s, spec.n_heads * spec.head_dim) @ cast(params["wo"], x.dtype)
    pad = cache_len - (s if spec.window is None else min(s, spec.window))
    ck = k if spec.window is None else k[:, -min(s, spec.window):]
    cv = v if spec.window is None else v[:, -min(s, spec.window):]
    ck = jnp.pad(ck, ((0, 0), (0, max(pad, 0)), (0, 0), (0, 0)))
    cv = jnp.pad(cv, ((0, 0), (0, max(pad, 0)), (0, 0), (0, 0)))
    cache = {
        "k": lsc(ck.swapaxes(1, 2), "batch", None, "kv_seq", None),
        "v": lsc(cv.swapaxes(1, 2), "batch", None, "kv_seq", None),
    }
    return lsc(y, "batch", None, None), cache


def _decode_sp_axes(cache_shape: tuple[int, ...]):
    """Physical axes carrying the decode cache's sequence dim, or None."""
    spec = logical_spec("batch", None, "kv_seq", None, shape=cache_shape)
    entries = list(spec) + [None] * (4 - len(spec))
    seq_axes = entries[2]
    if seq_axes is None:
        return None, None
    seq_axes = seq_axes if isinstance(seq_axes, tuple) else (seq_axes,)
    batch_axes = entries[0]
    if batch_axes is not None and not isinstance(batch_axes, tuple):
        batch_axes = (batch_axes,)
    return seq_axes, batch_axes


def _attention_decode_sp(
    spec: AttnSpec, q, k, v, cache: dict, pos, seq_axes, batch_axes
) -> tuple[jax.Array, dict]:
    """Sequence-parallel cached attention (decode-SP, shard_map).

    The KV cache's sequence dim is sharded over ``seq_axes`` (the model
    axis): each shard owns a contiguous chunk of ring-buffer slots, writes
    the new token *locally* iff it owns the slot, computes partial attention
    over its chunk, and the shards combine with a 3-term online-softmax psum
    — the cluster analogue of the RME assembling one cache line from
    parallel DRAM banks.  No all-gather of the cache, ever.
    """
    b = q.shape[0]  # q: (B, 1, H, Dh)
    kh = spec.n_kv_heads
    g = spec.n_heads // kh
    hd = spec.head_dim
    n_seq = 1
    for a in seq_axes:
        n_seq *= current_mesh_shape().get(a, 1)
    s_cache = cache["k"].shape[2]
    chunk = s_cache // n_seq
    bspec = batch_axes if batch_axes is None else (
        batch_axes if len(batch_axes) > 1 else batch_axes[0]
    )
    sspec = seq_axes if len(seq_axes) > 1 else seq_axes[0]
    cache_spec = jax.sharding.PartitionSpec(bspec, None, sspec, None)
    rep_spec = jax.sharding.PartitionSpec(bspec, None, None, None)

    def local(qh, kn, vn, ck, cv, pos):
        # qh (B,K,G,D) f32-scaled; kn/vn (B,K,1,D); ck/cv (B,K,chunk,D)
        idx = lax.axis_index(seq_axes)
        slot = pos % s_cache
        local_slot = slot - idx * chunk
        ok = (local_slot >= 0) & (local_slot < chunk)
        ls = jnp.clip(local_slot, 0, chunk - 1)
        cur_k = lax.dynamic_slice(ck, (0, 0, ls, 0), kn.shape)
        cur_v = lax.dynamic_slice(cv, (0, 0, ls, 0), vn.shape)
        ck = lax.dynamic_update_slice(ck, jnp.where(ok, kn, cur_k), (0, 0, ls, 0))
        cv = lax.dynamic_update_slice(cv, jnp.where(ok, vn, cur_v), (0, 0, ls, 0))
        k_pos = idx * chunk + jnp.arange(chunk)
        valid = k_pos <= pos
        logits = jnp.einsum("bkgd,bksd->bkgs", qh, ck.astype(F32))
        logits = jnp.where(valid[None, None, None, :], logits, MASK_VALUE)
        m = logits.max(axis=-1)  # (B,K,G)
        mg = lax.pmax(m, seq_axes)
        p = jnp.exp(logits - mg[..., None])
        l_part = p.sum(axis=-1)
        acc = jnp.einsum("bkgs,bksd->bkgd", p, cv.astype(F32))
        l_tot = lax.psum(l_part, seq_axes)
        acc_tot = lax.psum(acc, seq_axes)
        out = acc_tot / jnp.maximum(l_tot[..., None], 1e-30)
        return out, ck, cv

    out, ck, cv = shard_map(
        local,
        in_specs=(rep_spec, rep_spec, rep_spec, cache_spec, cache_spec,
                  jax.sharding.PartitionSpec()),
        out_specs=(rep_spec, cache_spec, cache_spec),
    )(
        (q * spec.scale).reshape(b, kh, g, hd).astype(F32),
        k.swapaxes(1, 2), v.swapaxes(1, 2), cache["k"], cache["v"], pos,
    )
    return out, {"k": ck, "v": cv}


def attention_decode(
    params: Params, spec: AttnSpec, x: jax.Array, cache: dict, pos: jax.Array
) -> tuple[jax.Array, dict]:
    """One-token cached attention. x (B,1,D); cache k/v (B,K,S,Dh); pos ().

    For windowed layers the cache is a ring buffer of size ``window``; the
    write slot is ``pos % window`` and the mask keeps the last ``window``
    positions — constant memory for gemma3-local / recurrentgemma-local at
    524k context.  When the active sharding rules place the cache's sequence
    dim on a mesh axis, the sequence-parallel shard_map path is used (local
    ring writes + online-softmax combine); otherwise the single-device path.
    """
    b = x.shape[0]
    s_cache = cache["k"].shape[2]
    pos_b = jnp.broadcast_to(pos, (b, 1))
    cos, sin = rope_cos_sin(
        pos_b if not spec.mrope else jnp.broadcast_to(pos, (b, 3, 1)),
        spec.head_dim, spec.rope_theta, spec.mrope,
    )
    q, k, v = _qkv(params, spec, x, cos, sin)
    kh = spec.n_kv_heads
    g = spec.n_heads // kh

    seq_axes, batch_axes = _decode_sp_axes(cache["k"].shape)
    if seq_axes is not None:
        out, new_cache = _attention_decode_sp(
            spec, q, k, v, cache, pos, seq_axes, batch_axes
        )
    else:
        # windowed layers use the cache as a ring buffer; full caches never
        # wrap (pos < s_cache), so one modular slot covers both
        slot = pos % s_cache
        ck = lax.dynamic_update_slice(
            cache["k"], k.swapaxes(1, 2), (0, 0, slot, 0)
        )
        cv = lax.dynamic_update_slice(
            cache["v"], v.swapaxes(1, 2), (0, 0, slot, 0)
        )
        qh = (q * spec.scale).reshape(b, kh, g, spec.head_dim).astype(F32)
        logits = jnp.einsum("bkgd,bksd->bkgs", qh, ck.astype(F32))
        # a ring slot only holds one of the last s_cache positions, so slot
        # validity reduces to "has this slot been written yet"
        k_pos = jnp.arange(s_cache)
        valid = k_pos <= pos
        logits = jnp.where(valid[None, None, None, :], logits, MASK_VALUE)
        w = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bkgs,bksd->bkgd", w, cv.astype(F32))
        new_cache = {"k": ck, "v": cv}
    out = out.reshape(b, 1, spec.n_heads * spec.head_dim).astype(x.dtype)
    y = out @ cast(params["wo"], x.dtype)
    return lsc(y, "batch", None, None), new_cache


def init_attention_cache(
    spec: AttnSpec, batch: int, max_len: int, dtype=jnp.bfloat16
) -> dict:
    s = min(max_len, spec.window) if spec.window is not None else max_len
    shape = (batch, spec.n_kv_heads, s, spec.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


# ------------------------------------------------------------------- FFNs
def init_mlp(key, d_model: int, d_ff: int, kind: str = "swiglu") -> Params:
    ks = jax.random.split(key, 3)
    s_in, s_out = d_model**-0.5, d_ff**-0.5
    if kind in ("swiglu", "geglu"):
        return {
            "w_gate": normal(ks[0], (d_model, d_ff), s_in),
            "w_up": normal(ks[1], (d_model, d_ff), s_in),
            "w_down": normal(ks[2], (d_ff, d_model), s_out),
        }
    return {  # vanilla transformer FFN (seamless encoder/decoder)
        "w_in": normal(ks[0], (d_model, d_ff), s_in),
        "w_down": normal(ks[1], (d_ff, d_model), s_out),
    }


def mlp(params: Params, x: jax.Array, kind: str = "swiglu") -> jax.Array:
    dt = x.dtype
    if kind in ("swiglu", "geglu"):
        act = jax.nn.silu if kind == "swiglu" else functools.partial(
            jax.nn.gelu, approximate=True
        )
        h = act(x @ cast(params["w_gate"], dt)) * (x @ cast(params["w_up"], dt))
        h = lsc(h, "batch", None, "mlp")
        return lsc(h @ cast(params["w_down"], dt), "batch", None, None)
    h = jax.nn.gelu(x @ cast(params["w_in"], dt), approximate=True)
    h = lsc(h, "batch", None, "mlp")
    return lsc(h @ cast(params["w_down"], dt), "batch", None, None)


# -------------------------------------------------------------------- MoE
@dataclasses.dataclass(frozen=True)
class MoESpec:
    d_model: int
    d_ff: int  # per-expert hidden
    n_experts: int
    top_k: int
    capacity_factor: float = 1.25


def init_moe(key, spec: MoESpec) -> Params:
    ks = jax.random.split(key, 4)
    e, d, f = spec.n_experts, spec.d_model, spec.d_ff
    return {
        "router": normal(ks[0], (d, e), d**-0.5),
        "expert_gate": normal(ks[1], (e, d, f), d**-0.5),
        "expert_up": normal(ks[2], (e, d, f), d**-0.5),
        "expert_down": normal(ks[3], (e, f, d), f**-0.5),
    }


def _moe_dispatch_compute(
    spec: MoESpec, xt: jax.Array, probs: jax.Array, wg, wu, wd,
    n_experts: int, expert_base: int, cap: int,
) -> jax.Array:
    """Capacity-bounded top-k dispatch + expert FFN + weighted combine.

    Handles a contiguous expert range [expert_base, expert_base+n_experts):
    tokens routed elsewhere are dropped here (another shard owns them).
    Everything is local compute: argsort, scatter, three matmuls, scatter-add.
    """
    t, d = xt.shape
    dt = xt.dtype
    k = spec.top_k
    gate, idx = lax.top_k(probs, k)  # (T, k) over the FULL expert domain
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

    local = idx - expert_base
    mine = (local >= 0) & (local < n_experts)
    slot_expert = jnp.where(mine, local, n_experts).reshape(t * k)  # E -> drop
    slot_token = jnp.repeat(jnp.arange(t), k)
    slot_gate = gate.reshape(t * k)
    order = jnp.argsort(slot_expert, stable=True)
    se = slot_expert[order]
    st = slot_token[order]
    sg = slot_gate[order]
    seg_start = jnp.searchsorted(se, jnp.arange(n_experts))
    rank = jnp.arange(t * k) - seg_start[jnp.minimum(se, n_experts - 1)]
    keep = (rank < cap) & (se < n_experts)
    dest = jnp.where(keep, se * cap + rank, n_experts * cap)  # OOB -> dropped

    buf = jnp.zeros((n_experts * cap, d), dt).at[dest].set(
        xt[st], mode="drop", unique_indices=True
    ).reshape(n_experts, cap, d)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wg))
    h = h * jnp.einsum("ecd,edf->ecf", buf, wu)
    out = jnp.einsum("ecf,efd->ecd", h, wd).reshape(n_experts * cap, d)

    gathered = jnp.where(
        keep[:, None], out.at[dest].get(mode="fill", fill_value=0), 0
    )
    return jnp.zeros((t, d), dt).at[st].add(gathered * sg[:, None].astype(dt))


def _moe_axes() -> tuple | None:
    """(expert_axes, fsdp_axes) when EP sharding rules are active."""
    rules = current_rules()
    if not rules:
        return None
    ea = rules.get("expert")
    if not ea:
        return None
    sizes = current_mesh_shape()
    n = 1
    for a in ea:
        n *= sizes.get(a, 1)
    if n <= 1:
        return None
    return tuple(ea), tuple(rules.get("fsdp") or ())


def moe_block(params: Params, spec: MoESpec, x: jax.Array) -> jax.Array:
    """Token-choice top-k MoE with sort-based, capacity-bounded dispatch.

    Distributed form (§Perf iteration 3): activations are replicated across
    the ``model`` (expert) axis, so dispatch needs NO collectives at all —
    each expert shard selects the tokens routed to ITS experts from its
    local copy (shard_map), runs the expert FFN on weights whose d_model dim
    is all-gathered across the FSDP axis (the only weight movement), and the
    per-shard partial outputs combine with one activation-sized psum.  This
    replaced a pjit scatter formulation whose dispatch buffers XLA could not
    partition (231 GiB/device peak on qwen3-moe → 84 MB local buffers).
    """
    b, s, d = x.shape
    dt = x.dtype
    t = b * s
    e, k = spec.n_experts, spec.top_k

    axes = _moe_axes()
    if axes is None:  # single-device / test path
        cap = max(int(math.ceil(spec.capacity_factor * k * t / e)), 4)
        xt = x.reshape(t, d)
        probs = jax.nn.softmax(
            (xt @ cast(params["router"], dt)).astype(F32), axis=-1
        )
        y = _moe_dispatch_compute(
            spec, xt, probs,
            cast(params["expert_gate"], dt), cast(params["expert_up"], dt),
            cast(params["expert_down"], dt), e, 0, cap,
        )
        return lsc(y.reshape(b, s, d), "batch", None, None)

    expert_axes, fsdp_axes = axes
    sizes = current_mesh_shape()
    n_shards = 1
    for a in expert_axes:
        n_shards *= sizes.get(a, 1)
    n_fsdp = 1
    for a in fsdp_axes:
        n_fsdp *= sizes.get(a, 1)
    e_local = e // n_shards
    f_ff = params["expert_down"].shape[-2]
    rules = current_rules()
    batch_axes = tuple(rules.get("batch") or ())
    bspec = (batch_axes if len(batch_axes) > 1 else batch_axes[0]) if batch_axes else None
    espec = expert_axes if len(expert_axes) > 1 else expert_axes[0]
    fspec = (fsdp_axes if len(fsdp_axes) > 1 else fsdp_axes[0]) if fsdp_axes else None
    P = jax.sharding.PartitionSpec

    # Mode decision (§Perf iteration 9): gathering weights moves ~3·E_l·D·F
    # bytes/shard; keeping weights stationary moves ~tokens·k·(D+F).  Train
    # steps (10^5-10^6 tokens) want the gather; decode (10^2 tokens) wants
    # stationary — the gather form costs 48 GB PER TOKEN STEP on llama4.
    stationary = fsdp_axes and (t * k < 3 * e_local * f_ff)

    def local_gather(xl, router, wg, wu, wd):
        bl, sl, _ = xl.shape
        tl = bl * sl
        cap = max(int(math.ceil(spec.capacity_factor * k * tl / e)), 4)
        xt = xl.reshape(tl, d)
        probs = jax.nn.softmax((xt @ cast(router, dt)).astype(F32), axis=-1)
        shard = lax.axis_index(expert_axes)
        base = shard * e_local
        # complete the weights' d_model dim across the FSDP axis (bf16)
        if fsdp_axes:
            wg = lax.all_gather(cast(wg, dt), fsdp_axes, axis=1, tiled=True)
            wu = lax.all_gather(cast(wu, dt), fsdp_axes, axis=1, tiled=True)
            wd = lax.all_gather(cast(wd, dt), fsdp_axes, axis=2, tiled=True)
        else:
            wg, wu, wd = cast(wg, dt), cast(wu, dt), cast(wd, dt)
        y = _moe_dispatch_compute(spec, xt, probs, wg, wu, wd,
                                  e_local, base, cap)
        # every shard produced the partial output of ITS experts
        y = lax.psum(y, expert_axes)
        return y.reshape(bl, sl, d)

    def local_stationary(xl, router, wg, wu, wd):
        """Decode-sized MoE: tokens travel, the (huge) weights never do.

        All tokens are gathered to every shard (KBs), each (expert, d-slice)
        shard contracts its local weight block, partial activations psum
        across the FSDP axis and expert outputs psum across the expert axis
        — total wire per layer ≈ tokens·(D+F) bytes instead of 3·E_l·D·F.
        """
        bl, sl, _ = xl.shape
        xg = lax.all_gather(xl, batch_axes, axis=0, tiled=True) if batch_axes else xl
        tg = xg.shape[0] * sl
        cap = max(int(math.ceil(spec.capacity_factor * k * tg / e)), 4)
        xt = xg.reshape(tg, d)
        probs = jax.nn.softmax((xt @ cast(router, dt)).astype(F32), axis=-1)
        shard = lax.axis_index(expert_axes)
        base = shard * e_local
        fshard = lax.axis_index(fsdp_axes)
        d_slice = d // n_fsdp
        gate, idx = lax.top_k(probs, k)
        gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
        local_e = idx - base
        mine = (local_e >= 0) & (local_e < e_local)
        slot_e = jnp.where(mine, local_e, e_local).reshape(tg * k)
        slot_t = jnp.repeat(jnp.arange(tg), k)
        slot_g = gate.reshape(tg * k)
        order = jnp.argsort(slot_e, stable=True)
        se, st, sg = slot_e[order], slot_t[order], slot_g[order]
        seg = jnp.searchsorted(se, jnp.arange(e_local))
        rank = jnp.arange(tg * k) - seg[jnp.minimum(se, e_local - 1)]
        keep = (rank < cap) & (se < e_local)
        dest = jnp.where(keep, se * cap + rank, e_local * cap)
        # dispatch only my d-slice of each token
        xt_slice = lax.dynamic_slice(xt, (0, fshard * d_slice), (tg, d_slice))
        buf = jnp.zeros((e_local * cap, d_slice), dt).at[dest].set(
            xt_slice[st], mode="drop", unique_indices=True
        ).reshape(e_local, cap, d_slice)
        # partial hidden from my d-slice; complete across the FSDP axis
        h = jnp.einsum("ecd,edf->ecf", buf, cast(wg, dt))
        hu = jnp.einsum("ecd,edf->ecf", buf, cast(wu, dt))
        h = lax.psum(jnp.stack([h, hu]), fsdp_axes)
        h = jax.nn.silu(h[0]) * h[1]
        out = jnp.einsum("ecf,efd->ecd", h, cast(wd, dt))  # (E_l, cap, d_slice)
        out = out.reshape(e_local * cap, d_slice)
        gathered = jnp.where(
            keep[:, None], out.at[dest].get(mode="fill", fill_value=0), 0
        )
        y = jnp.zeros((tg, d_slice), dt).at[st].add(
            gathered * sg[:, None].astype(dt)
        )
        y = lax.psum(y, expert_axes)  # combine expert shards
        # reassemble full D, then take my batch rows back
        y = lax.all_gather(y, fsdp_axes, axis=1, tiled=True)  # (tg, D)
        tl = bl * sl
        bshard = lax.axis_index(batch_axes) if batch_axes else 0
        y = lax.dynamic_slice(y, (bshard * tl, 0), (tl, d))
        return y.reshape(bl, sl, d)

    y = shard_map(
        local_stationary if stationary else local_gather,
        in_specs=(
            P(bspec, None, None),  # x: batch-sharded, replicated over model
            P(),  # router (small, replicated)
            P(espec, fspec, None),  # (E, D, F)
            P(espec, fspec, None),
            P(espec, None, fspec),  # (E, F, D)
        ),
        out_specs=P(bspec, None, None),
    )(x, params["router"], params["expert_gate"], params["expert_up"],
      params["expert_down"])
    return lsc(y, "batch", None, None)


def moe_aux_loss(params: Params, spec: MoESpec, x: jax.Array) -> jax.Array:
    """Switch-style load-balancing loss (mean over tokens)."""
    b, s, d = x.shape
    xt = x.reshape(b * s, d)
    logits = (xt @ cast(params["router"], x.dtype)).astype(F32)
    probs = jax.nn.softmax(logits, axis=-1)
    top1 = jnp.argmax(probs, axis=-1)
    frac = jnp.mean(jax.nn.one_hot(top1, spec.n_experts, dtype=F32), axis=0)
    imp = jnp.mean(probs, axis=0)
    return spec.n_experts * jnp.sum(frac * imp)


# --------------------------------------------------------- depthwise conv
def causal_conv1d(
    x: jax.Array, kernel: jax.Array, state: jax.Array | None = None
) -> tuple[jax.Array, jax.Array]:
    """Causal depthwise conv. x (B,S,C), kernel (W,C). Returns (y, new_state).

    Implemented as W shifted adds (W is 4): cheap, fusion-friendly, no conv
    primitive.  ``state`` is the last W-1 inputs for streaming decode.
    """
    w = kernel.shape[0]
    if state is None:
        state = jnp.zeros((x.shape[0], w - 1, x.shape[2]), x.dtype)
    ext = jnp.concatenate([state, x], axis=1)  # (B, S+W-1, C)
    y = sum(
        ext[:, i : i + x.shape[1]] * cast(kernel[i], x.dtype)[None, None, :]
        for i in range(w)
    )
    return y, ext[:, -(w - 1):]


# ---------------------------------------------------------------- Mamba-2
@dataclasses.dataclass(frozen=True)
class SSDSpec:
    d_model: int
    d_state: int = 128
    head_dim: int = 64
    expand: int = 2
    conv_width: int = 4
    chunk: int = 256
    n_groups: int = 1

    @property
    def d_inner(self) -> int:
        return self.expand * self.d_model

    @property
    def n_heads(self) -> int:
        return self.d_inner // self.head_dim


def init_ssd(key, spec: SSDSpec) -> Params:
    d, di, n, h = spec.d_model, spec.d_inner, spec.d_state, spec.n_heads
    g = spec.n_groups
    ks = jax.random.split(key, 5)
    conv_ch = di + 2 * g * n
    return {
        # fused input projection: [z, x, B, C, dt]
        "w_zx": normal(ks[0], (d, 2 * di + 2 * g * n + h), d**-0.5),
        "conv_kernel": normal(ks[1], (spec.conv_width, conv_ch), conv_ch**-0.5),
        "a_log": jnp.log(jnp.linspace(1.0, 16.0, h).astype(F32)),
        "dt_bias": jnp.log(jnp.expm1(jnp.full((h,), 1e-2, F32))),  # softplus^-1
        "d_skip": jnp.ones((h,), F32),
        "norm": init_rms_norm(di),
        "w_out": normal(ks[4], (di, d), di**-0.5),
    }


def _ssd_split(params, spec: SSDSpec, x):
    """Input projection + causal conv; returns z, xh, Bm, Cm, dt."""
    b, s, _ = x.shape
    di, n, h, g = spec.d_inner, spec.d_state, spec.n_heads, spec.n_groups
    dt_ = x.dtype
    zxbcdt = x @ cast(params["w_zx"], dt_)
    z = zxbcdt[..., :di]
    xbc = zxbcdt[..., di : di + di + 2 * g * n]
    dt = zxbcdt[..., -h:]
    return z, xbc, dt


def _ssd_post(params, spec, y, z):
    y = rms_norm(y * jax.nn.silu(z.astype(F32)).astype(y.dtype),
                 params["norm"]["scale"])
    return lsc(y @ cast(params["w_out"], y.dtype), "batch", None, None)


def ssd_block(
    params: Params, spec: SSDSpec, x: jax.Array, return_state: bool = False
):
    """Mamba-2 SSD, chunked "state-space duality" form (matmuls on the MXU).

    Within a chunk the recurrence is an attention-like masked contraction;
    across chunks a tiny sequential scan carries the (H, P, N) state.  This is
    the TPU-native adaptation: the GPU implementation leans on fused Triton
    scans, the SSD matmul form maps straight onto the MXU.
    """
    b, s, _ = x.shape
    di, n, h, p = spec.d_inner, spec.d_state, spec.n_heads, spec.head_dim
    if spec.n_groups != 1:
        raise NotImplementedError("SSD is implemented for n_groups=1 (mamba2 default)")
    q = min(spec.chunk, s)
    pad = (-s) % q
    s_real = s
    if pad:  # pad to a chunk multiple; padded steps are frozen via dt=0 below
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        s = s + pad
    nc = s // q

    z, xbc, dt = _ssd_split(params, spec, x)
    xbc_pre = jax.nn.silu(xbc)
    xbc, conv_state = causal_conv1d(xbc_pre, params["conv_kernel"])
    if pad and return_state:  # conv state = last W-1 *valid* inputs
        w = params["conv_kernel"].shape[0]
        ext = jnp.concatenate(
            [jnp.zeros((b, w - 1, xbc_pre.shape[2]), xbc_pre.dtype),
             xbc_pre[:, :s_real]], axis=1,
        )
        conv_state = ext[:, -(w - 1):]
    xh = xbc[..., :di]
    bm = xbc[..., di : di + n]  # (B,S,N), single group
    cm = xbc[..., di + n :]  # (B,S,N)

    dt = jax.nn.softplus(dt.astype(F32) + params["dt_bias"])  # (B,S,H)
    if pad:  # dt=0 on padding: decay=1 and zero input — state passes through
        valid = (jnp.arange(s) < s_real).astype(F32)
        dt = dt * valid[None, :, None]
    a = -jnp.exp(params["a_log"])  # (H,)
    log_decay = dt * a  # (B,S,H) = log a_t  (negative)

    xh = xh.reshape(b, s, h, p)
    xdt = xh.astype(F32) * dt[..., None]  # dt-weighted input

    # chunk views
    xc = xdt.reshape(b, nc, q, h, p)
    bc = bm.reshape(b, nc, q, n).astype(F32)
    cc = cm.reshape(b, nc, q, n).astype(F32)
    ld = log_decay.reshape(b, nc, q, h)
    cum = jnp.cumsum(ld, axis=2)  # (B,nc,Q,H) inclusive cumulative log decay
    total = cum[:, :, -1]  # (B,nc,H)

    # ---- intra-chunk: M[q,k,h] = (C_q . B_k) * exp(cum_q - cum_k) * causal
    gl = jnp.einsum("bcqn,bckn->bcqk", cc, bc)  # (b,nc,Q,K)
    decay = cum[:, :, :, None, :] - cum[:, :, None, :, :]  # (b,nc,Q,K,H)
    causal = jnp.tril(jnp.ones((q, q), bool))
    m = jnp.where(
        causal[None, None, :, :, None], jnp.exp(decay) * gl[..., None], 0.0
    )
    m = lsc(m, "batch", None, None, None, "heads")
    y_intra = jnp.einsum("bcqkh,bckhp->bcqhp", m, xc)

    # ---- chunk states: S_c = sum_k B_k ⊗ x_k * exp(total - cum_k)
    w = jnp.exp(total[:, :, None, :] - cum)  # (b,nc,Q,H)
    states = jnp.einsum("bcqn,bcqhp,bcqh->bchpn", bc, xc, w)
    states = lsc(states, "batch", None, "heads", None, None)

    # ---- inter-chunk scan (nc steps, tiny state)
    def scan_fn(h_prev, inp):
        st, tot = inp  # (b,h,p,n), (b,h)
        h_new = h_prev * jnp.exp(tot)[:, :, None, None] + st
        return h_new, h_prev

    h0 = jnp.zeros((b, h, p, n), F32)
    h_final, h_prevs = lax.scan(
        scan_fn, h0, (states.swapaxes(0, 1), total.swapaxes(0, 1))
    )  # h_prevs: (nc, b, h, p, n) = state entering each chunk
    h_prevs = h_prevs.swapaxes(0, 1)  # (b, nc, h, p, n)

    # ---- inter-chunk contribution: Y_inter[q] = (C_q . h_prev) * exp(cum_q)
    y_inter = jnp.einsum("bcqn,bchpn,bcqh->bcqhp", cc, h_prevs, jnp.exp(cum))

    y = (y_intra + y_inter).reshape(b, s, h, p)
    y = y + xh.astype(F32) * params["d_skip"][None, None, :, None]
    y = y.reshape(b, s, di).astype(x.dtype)
    if pad:
        y, z = y[:, :s_real], z[:, :s_real]
    out = _ssd_post(params, spec, y, z)
    if return_state:
        return out, {"conv": conv_state, "ssm": h_final}
    return out


def init_ssd_state(spec: SSDSpec, batch: int, dtype=jnp.float32) -> dict:
    g = spec.n_groups
    return {
        "conv": jnp.zeros(
            (batch, spec.conv_width - 1, spec.d_inner + 2 * g * spec.d_state),
            jnp.bfloat16,
        ),
        "ssm": jnp.zeros((batch, spec.n_heads, spec.head_dim, spec.d_state), dtype),
    }


def ssd_decode(
    params: Params, spec: SSDSpec, x: jax.Array, state: dict
) -> tuple[jax.Array, dict]:
    """Single-token SSD step: h = a*h + B ⊗ (dt*x);  y = C.h + D*x."""
    b = x.shape[0]
    di, n, h, p = spec.d_inner, spec.d_state, spec.n_heads, spec.head_dim
    z, xbc, dt = _ssd_split(params, spec, x)
    xbc, conv_state = causal_conv1d(
        jax.nn.silu(xbc), params["conv_kernel"], state["conv"]
    )
    xh = xbc[:, 0, :di].reshape(b, h, p).astype(F32)
    bm = xbc[:, 0, di : di + n].astype(F32)  # (B,N), single group
    cm = xbc[:, 0, di + n :].astype(F32)  # (B,N)
    dt = jax.nn.softplus(dt[:, 0].astype(F32) + params["dt_bias"])  # (B,H)
    a = jnp.exp(dt * -jnp.exp(params["a_log"]))  # (B,H)
    xdt = xh * dt[..., None]  # (B,H,P)
    h_new = state["ssm"] * a[..., None, None] + xdt[..., None] * bm[:, None, None, :]
    y = jnp.einsum("bhpn,bn->bhp", h_new, cm)
    y = y + xh * params["d_skip"][None, :, None]
    y = y.reshape(b, 1, di).astype(x.dtype)
    out = _ssd_post(params, spec, y, z)
    return out, {"conv": conv_state, "ssm": h_new}


# ----------------------------------------------------------------- RG-LRU
@dataclasses.dataclass(frozen=True)
class RGLRUSpec:
    d_model: int
    lru_width: int
    conv_width: int = 4
    c: float = 8.0  # the paper's fixed temperature


def init_rglru(key, spec: RGLRUSpec) -> Params:
    d, w = spec.d_model, spec.lru_width
    ks = jax.random.split(key, 6)
    # Λ init so that a = sigmoid(Λ)^c lands in [0.9, 0.999] (griffin init)
    u = jax.random.uniform(ks[0], (w,), F32, 0.9**2, 0.999**2)
    lam = jnp.log(u ** (1.0 / spec.c) / (1 - u ** (1.0 / spec.c)))
    return {
        "w_branch": normal(ks[1], (d, 2 * w), d**-0.5),  # [gate branch, rec branch]
        "conv_kernel": normal(ks[2], (spec.conv_width, w), w**-0.5),
        "w_a": normal(ks[3], (w, w), w**-0.5),  # recurrence gate
        "b_a": jnp.zeros((w,), F32),
        "w_x": normal(ks[4], (w, w), w**-0.5),  # input gate
        "b_x": jnp.zeros((w,), F32),
        "lambda_": lam,
        "w_out": normal(ks[5], (w, d), w**-0.5),
    }


def _rglru_gates(params, spec, xr):
    """Per-step gate math shared by scan and decode. xr (…, W) f32."""
    r = jax.nn.sigmoid(xr @ cast(params["w_a"], F32) + cast(params["b_a"], F32))
    i = jax.nn.sigmoid(xr @ cast(params["w_x"], F32) + cast(params["b_x"], F32))
    log_a = -spec.c * r * jax.nn.softplus(params["lambda_"])  # (…, W)
    a = jnp.exp(log_a)
    mult = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-12))
    return a, mult * i * xr


def rglru_block(
    params: Params, spec: RGLRUSpec, x: jax.Array, return_state: bool = False
):
    """Griffin recurrent block: conv → RG-LRU (associative scan) → gate-mix."""
    b, s, d = x.shape
    dt = x.dtype
    branches = x @ cast(params["w_branch"], dt)
    gate = jax.nn.gelu(branches[..., : spec.lru_width], approximate=True)
    xr, conv_state = causal_conv1d(
        branches[..., spec.lru_width :], params["conv_kernel"]
    )
    xr = lsc(xr, "batch", None, "mlp").astype(F32)

    a, bterm = _rglru_gates(params, spec, xr)  # (B,S,W) each

    def combine(e1, e2):
        a1, b1 = e1
        a2, b2 = e2
        return a1 * a2, a2 * b1 + b2

    _, h = lax.associative_scan(combine, (a, bterm), axis=1)
    y = (h.astype(dt) * gate)
    y = lsc(y, "batch", None, "mlp")
    out = lsc(y @ cast(params["w_out"], dt), "batch", None, None)
    if return_state:
        return out, {"conv": conv_state, "h": h[:, -1]}
    return out


def init_rglru_state(spec: RGLRUSpec, batch: int) -> dict:
    return {
        "conv": jnp.zeros((batch, spec.conv_width - 1, spec.lru_width), jnp.bfloat16),
        "h": jnp.zeros((batch, spec.lru_width), F32),
    }


def rglru_decode(
    params: Params, spec: RGLRUSpec, x: jax.Array, state: dict
) -> tuple[jax.Array, dict]:
    b, _, d = x.shape
    dt = x.dtype
    branches = x @ cast(params["w_branch"], dt)
    gate = jax.nn.gelu(branches[..., : spec.lru_width], approximate=True)
    xr, conv_state = causal_conv1d(
        branches[..., spec.lru_width :], params["conv_kernel"], state["conv"]
    )
    xr = xr[:, 0].astype(F32)
    a, bterm = _rglru_gates(params, spec, xr)
    h = a * state["h"] + bterm
    y = (h[:, None, :].astype(dt) * gate)
    return (
        lsc(y @ cast(params["w_out"], dt), "batch", None, None),
        {"conv": conv_state, "h": h},
    )
