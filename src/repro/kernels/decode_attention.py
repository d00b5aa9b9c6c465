"""Pallas TPU kernel: fused flash-decode attention over an int8 KV cache.

Motivation (§Perf decode iterations): XLA-auto lowering of int8-KV decode
materializes the dequantized bf16 cache in HBM (measured 70 GiB/dev on
minicpm decode_32k), defeating the quantization.  This kernel streams
int8 K/V blocks HBM→VMEM, dequantizes IN VMEM, and runs the online-softmax
accumulation — the dequantized cache never exists in HBM, so the decode
memory term gets the full int8 saving (1.78×).

Contract (cache part of one decode step, per layer):

    out_w, m, l = fused_decode_attention(q, k_q, k_s, v_q, v_s, length)

  q:    (b, kvh, g, hd)        — one new token's queries, GQA-grouped
  k_q:  (b, S, kvh, hd) int8   — quantized keys,  k_s (b, S, kvh) scales
  v_q:  (b, S, kvh, hd) int8   — quantized values, v_s (b, S, kvh) scales
  length: scalar int32         — valid prefix (positions >= length masked)

Returns the UNNORMALIZED flash state over the cache: ``out_w`` =
Σ softmax-weights·V before division, with row max ``m`` and denominator
``l`` — the caller merges the new token's own K/V via the standard
two-softmax combine (see serve/decode.py), keeping the kernel oblivious
to the cache-update policy.

Grid: ``(b, kvh, S//block_s)`` — the S dimension is the reduction, scanned
with VMEM scratch carries (m, l, acc).  VMEM per step: one
``(block_s, hd)`` int8 K block + V block + scales + (g, block_s) scores:
< 0.5 MiB at block_s=512, hd=128.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(
    len_ref,       # scalar prefetch: (1,) int32 valid length
    q_ref,         # VMEM (1, 1, g, hd)
    kq_ref,        # VMEM (1, block_s, 1, hd) int8
    ks_ref,        # VMEM (1, block_s, 1)
    vq_ref,        # VMEM (1, block_s, 1, hd) int8
    vs_ref,        # VMEM (1, block_s, 1)
    out_ref,       # VMEM (1, 1, g, hd) f32 — unnormalized
    m_ref,         # VMEM (1, 1, g) f32
    l_ref,         # VMEM (1, 1, g) f32
    acc_ref,       # scratch VMEM (g, hd) f32
    m_scr,         # scratch VMEM (g, 1) f32
    l_scr,         # scratch VMEM (g, 1) f32
    *,
    block_s: int,
    num_blocks: int,
    scale: float,
):
    s_idx = pl.program_id(2)

    @pl.when(s_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_scr[...] = jnp.full_like(m_scr, -1e30)
        l_scr[...] = jnp.zeros_like(l_scr)

    q = q_ref[0, 0].astype(jnp.float32)                        # (g, hd)
    k = kq_ref[0, :, 0].astype(jnp.float32) * ks_ref[0, :, 0].astype(jnp.float32)[:, None]
    v = vq_ref[0, :, 0].astype(jnp.float32) * vs_ref[0, :, 0].astype(jnp.float32)[:, None]

    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale   # (g, block_s)
    pos = s_idx * block_s + jax.lax.broadcasted_iota(jnp.int32, (1, block_s), 1)
    s = jnp.where(pos < len_ref[0], s, -1e30)

    m_prev = m_scr[...]                                        # (g, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    corr = jnp.exp(m_prev - m_new)
    w = jnp.exp(s - m_new)                                     # (g, block_s)
    l_scr[...] = l_scr[...] * corr + w.sum(axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jnp.dot(
        w, v, preferred_element_type=jnp.float32
    )
    m_scr[...] = m_new

    @pl.when(s_idx == num_blocks - 1)
    def _flush():
        out_ref[0, 0] = acc_ref[...]
        m_ref[0, 0] = m_scr[..., 0]
        l_ref[0, 0] = l_scr[..., 0]


def fused_decode_attention_pallas(
    q: jax.Array,        # (b, kvh, g, hd)
    k_q: jax.Array,      # (b, S, kvh, hd) int8
    k_s: jax.Array,      # (b, S, kvh)
    v_q: jax.Array,
    v_s: jax.Array,
    length: jax.Array,   # scalar int32
    *,
    block_s: int = 512,
    interpret: bool | None = None,
):
    b, kvh, g, hd = q.shape
    S = k_q.shape[1]
    if S % block_s != 0:
        raise ValueError(f"S={S} must be a multiple of block_s={block_s}")
    if hd % 128 != 0 and hd < 128:
        # small head dims still work (lanes pad); only assert sanity
        pass
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    num_blocks = S // block_s
    scale = 1.0 / (hd ** 0.5)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, kvh, num_blocks),
        in_specs=[
            pl.BlockSpec((1, 1, g, hd), lambda bi, hi, si, ln: (bi, hi, 0, 0)),
            pl.BlockSpec((1, block_s, 1, hd), lambda bi, hi, si, ln: (bi, si, hi, 0)),
            pl.BlockSpec((1, block_s, 1), lambda bi, hi, si, ln: (bi, si, hi)),
            pl.BlockSpec((1, block_s, 1, hd), lambda bi, hi, si, ln: (bi, si, hi, 0)),
            pl.BlockSpec((1, block_s, 1), lambda bi, hi, si, ln: (bi, si, hi)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, g, hd), lambda bi, hi, si, ln: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, g), lambda bi, hi, si, ln: (bi, hi, 0)),
            pl.BlockSpec((1, 1, g), lambda bi, hi, si, ln: (bi, hi, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((g, hd), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _kernel, block_s=block_s, num_blocks=num_blocks, scale=scale
    )
    out_shapes = [
        jax.ShapeDtypeStruct((b, kvh, g, hd), jnp.float32),
        jax.ShapeDtypeStruct((b, kvh, g), jnp.float32),
        jax.ShapeDtypeStruct((b, kvh, g), jnp.float32),
    ]
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shapes,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(jnp.reshape(length.astype(jnp.int32), (1,)), q, k_q, k_s, v_q, v_s)
