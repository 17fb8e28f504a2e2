"""Flash-decode attention Pallas kernel (grouped GQA + fused int8-KV dequant).

The §Perf cell-A analysis showed memory-bound decode is dominated by KV-cache
streaming plus the materialized f32 score pipeline. This kernel is the
TPU-native fix: one `pallas_call` whose grid walks KV blocks with an
online-softmax accumulator held in VMEM scratch, so per step it

  * streams each cache byte from HBM exactly once (int8 or bf16 storage),
  * dequantizes int8 KV *in-register* next to the MXU dot (the paper's
    Approximator placement, applied to attention),
  * evaluates all `rep` grouped query heads against each KV head block
    without materializing repeats,
  * never writes scores/probabilities back to HBM (block-local VMEM only).

Grid: (batch, kv_head, s_blocks) — s innermost so the (m, l, acc) scratch
carries across cache blocks; the output block is written on the last step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
NEG = -1e30


def _kernel(len_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref,
            m_ref, l_ref, acc_ref, *, block_s: int, n_blocks: int,
            quant: bool, scale: float):
    sb = pl.program_id(2)

    @pl.when(sb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(F32)  # [rep, dh]
    k = k_ref[0, :, 0]  # [bs, dh] int8|bf16
    v = v_ref[0, :, 0]
    if quant:
        k = k.astype(F32) * ks_ref[0, :, 0].astype(F32)[:, None]
        v = v.astype(F32) * vs_ref[0, :, 0].astype(F32)[:, None]
    else:
        k = k.astype(F32)
        v = v.astype(F32)

    s = jnp.dot(q, k.T, preferred_element_type=F32) * scale  # [rep, bs]
    pos = sb * block_s + jax.lax.broadcasted_iota(jnp.int32, (1, block_s), 1)
    valid = pos < len_ref[0]
    s = jnp.where(valid, s, NEG)

    m_prev = m_ref[...]  # [rep]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=-1)
    acc_ref[...] = acc_ref[...] * corr[:, None] + jnp.dot(
        p, v, preferred_element_type=F32)
    m_ref[...] = m_new

    @pl.when(sb == n_blocks - 1)
    def _finalize():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)[:, None]
        o_ref[0, 0] = out.astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_s", "interpret"))
def decode_attention(
    q: jnp.ndarray,  # [B, KV, rep, dh] (current step's grouped queries)
    k_cache: jnp.ndarray,  # [B, S, KV, dh] bf16 or int8
    v_cache: jnp.ndarray,  # [B, S, KV, dh]
    kv_len: jnp.ndarray,  # [] int32 — valid cache length (mask beyond)
    k_scale: jnp.ndarray = None,  # [B, S, KV] when int8
    v_scale: jnp.ndarray = None,
    *,
    block_s: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    b, kv, rep, dh = q.shape
    s = k_cache.shape[1]
    quant = k_cache.dtype == jnp.int8
    bs = min(block_s, s)
    pad = (-s) % bs
    if pad:  # masked by kv_len anyway
        k_cache = jnp.pad(k_cache, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, pad), (0, 0), (0, 0)))
        if quant:
            k_scale = jnp.pad(k_scale, ((0, 0), (0, pad), (0, 0)))
            v_scale = jnp.pad(v_scale, ((0, 0), (0, pad), (0, 0)))
    sp = s + pad
    nb = sp // bs
    if not quant:  # dummy scale operands keep one kernel signature
        k_scale = jnp.zeros((b, sp, kv), jnp.bfloat16)
        v_scale = jnp.zeros((b, sp, kv), jnp.bfloat16)
    lens = jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32), (1,))

    grid = (b, kv, nb)
    out = pl.pallas_call(
        functools.partial(_kernel, block_s=bs, n_blocks=nb, quant=quant,
                          scale=dh**-0.5),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1,), lambda bi, g, sb: (0,)),
            pl.BlockSpec((1, 1, rep, dh), lambda bi, g, sb: (bi, g, 0, 0)),
            pl.BlockSpec((1, bs, 1, dh), lambda bi, g, sb: (bi, sb, g, 0)),
            pl.BlockSpec((1, bs, 1, dh), lambda bi, g, sb: (bi, sb, g, 0)),
            pl.BlockSpec((1, bs, 1), lambda bi, g, sb: (bi, sb, g)),
            pl.BlockSpec((1, bs, 1), lambda bi, g, sb: (bi, sb, g)),
        ],
        out_specs=pl.BlockSpec((1, 1, rep, dh), lambda bi, g, sb: (bi, g, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, kv, rep, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((rep,), F32),
            pltpu.VMEM((rep,), F32),
            pltpu.VMEM((rep, dh), F32),
        ],
        interpret=interpret,
    )(lens, q, k_cache, v_cache, k_scale, v_scale)
    return out


__all__ = ["decode_attention"]
