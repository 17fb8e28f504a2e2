"""Pure-jnp oracles for every Pallas kernel in this package.

These re-express each kernel with stock jax.lax/jnp ops (no Pallas) and are
the ground truth for the allclose sweeps in tests/test_kernels_*.py.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.common import requant_clip


def depthwise_conv_q_ref(x_q, w_q, mult, zcorr, bias_q, *, kernel=3, stride=1,
                         qmax=15, clip=True):
    """Oracle for kernels.depthwise_conv.depthwise_conv_q."""
    acc = jax.lax.conv_general_dilated(
        x_q.astype(jnp.int32),
        w_q.reshape(kernel, kernel, 1, -1).astype(jnp.int32),
        window_strides=(stride, stride),
        padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=x_q.shape[-1],
        preferred_element_type=jnp.int32,
    )
    return requant_clip(acc, mult, zcorr, bias_q, qmax, clip)


def fused_irb_q_ref(
    x_q,
    w1_q, mult1, zpc1, bias1,
    w2_q, mult2, zcorr2, bias2,
    w3_q, mult3, zpc3, bias3,
    *,
    kernel=3,
    stride=1,
    qmax=15,
    residual=False,
    res_scale=None,  # (a_z, a_s/y_s, b_z, b_s/y_s, round(y_z)) skip add
):
    """Oracle for kernels.fused_irb.fused_irb_q: pw-expand -> dw -> pw-project."""
    # stage 1: pointwise expansion (ReLU6 fused), integer zero-point term
    acc1 = jnp.einsum(
        "bhwc,ce->bhwe", x_q.astype(jnp.int32), w1_q.astype(jnp.int32),
        preferred_element_type=jnp.int32,
    ) + zpc1
    e = requant_clip(acc1, mult1, 0.0, bias1, qmax, clip=True)
    # stage 2: depthwise (ReLU6 fused)
    d = depthwise_conv_q_ref(
        e, w2_q, mult2, zcorr2, bias2, kernel=kernel, stride=stride, qmax=qmax,
        clip=True,
    )
    # stage 3: pointwise projection (linear -> asymmetric output quant)
    acc3 = jnp.einsum(
        "bhwe,eo->bhwo", d.astype(jnp.int32), w3_q.astype(jnp.int32),
        preferred_element_type=jnp.int32,
    ) + zpc3
    y = requant_clip(acc3, mult3, 0.0, bias3, qmax, clip=True)
    if residual:
        a_z, r_a, b_z, r_b, zy = res_scale
        a = (x_q.astype(jnp.float32) + a_z) * r_a
        bq = (y.astype(jnp.float32) + b_z) * r_b
        y = jnp.clip(jnp.round(a + bq) - zy, 0, qmax).astype(jnp.int32)
    return y


def quant_matmul_ref(x, w_q, w_scale, *, bits=8, group_size=None):
    """Oracle for kernels.quant_matmul.quant_matmul.

    x: [M, K] float; w_q int8 [K, N] (already unpacked); w_scale [N] or
    [K//group_size, N] for grouped quantization. y = x @ (w_q * scale).
    """
    if group_size is None:
        w = w_q.astype(jnp.float32) * w_scale[None, :]
    else:
        k, n = w_q.shape
        w = (
            w_q.astype(jnp.float32).reshape(k // group_size, group_size, n)
            * w_scale[:, None, :]
        ).reshape(k, n)
    return jnp.dot(x.astype(jnp.float32), w, preferred_element_type=jnp.float32)


def decode_attention_ref(q, k_cache, v_cache, kv_len, k_scale=None,
                         v_scale=None):
    """Oracle for kernels.decode_attention: grouped online-softmax decode.

    q [B,KV,rep,dh]; caches [B,S,KV,dh] (int8 with [B,S,KV] scales or bf16).
    """
    b, kv, rep, dh = q.shape
    s = k_cache.shape[1]
    if k_cache.dtype == jnp.int8:
        k = k_cache.astype(jnp.float32) * k_scale.astype(jnp.float32)[..., None]
        v = v_cache.astype(jnp.float32) * v_scale.astype(jnp.float32)[..., None]
    else:
        k, v = k_cache.astype(jnp.float32), v_cache.astype(jnp.float32)
    scores = jnp.einsum("bgrd,bsgd->bgrs", q.astype(jnp.float32), k) * dh**-0.5
    mask = jnp.arange(s)[None, None, None, :] < kv_len
    scores = jnp.where(mask, scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bgrs,bsgd->bgrd", w, v).astype(q.dtype)


__all__ = ["depthwise_conv_q_ref", "fused_irb_q_ref", "quant_matmul_ref",
           "decode_attention_ref"]
