"""Int8 KV-cache quantization for long-context decode.

The decode step streams two tensors from HBM every token: the weights
(halved by ops/wquant.py) and the KV cache. At short max_len the
weights dominate, but the cache grows linearly with context — at
GPT-2-125M geometry, B=8 x max_len=4096 is ~1.2 GB bf16, several times
the weight stream — so long-context serving is KV-bandwidth-bound and
int8 codes halve the dominant term.

Scheme: symmetric per-(position, head) scales — each cached K/V vector
[head_dim] gets one f32 scale (amax/127), stored in a parallel
[..., 1] buffer (moved with the codes into the cache layout
[..., H, 1, S] by models/decoding.pack_kv). Quantization happens at
WRITE time (one new vector
per step; the prompt bulk at prefill). At READ time the codes are NOT
dequantized to HBM — there are two read paths, both keeping int8 as
the only HBM-resident form. The dense path
(decoding.dense_decode_attend) keeps the int8 buffers as the attention
einsums' operands and applies K's scales to the logits and V's to the
probabilities (scale-on-scores factoring:
sum_d q_d*(K_kd*s_k) == (sum_d q_d*K_kd)*s_k). The flash path
(ops/flash_decode.py, the default on TPU for long caches) moves each
live int8 block into VMEM and applies the same factoring there, the
block's scales arriving as one lane row — with the added length-aware
win that dead blocks never cross the wire at all. What
is never done: dequantizing the full cache slice before attending.
The first design did, betting XLA would fuse the convert+mul into the
einsum's operand read the way it does for int8 weights (wquant.py) —
the r05 chip A/B measured that at 0.73x the bf16 baseline (XLA
materializes the dequantized [B, S, H, D] tensor in HBM: int8 read +
bf16 write + bf16 read).

Integration: decoding.decode_layer_scan carries the scale buffers and
the per-family caches gain "ks"/"vs" entries (transformer.init_kv_cache
/ llama.init_kv_cache with ``kv_int8=True``); attend_fns receive
``(codes, scales)`` tuples that grouped_decode_attend consumes. The
reference has no serving stack (SURVEY.md SS0); this serves the
framework goal's perf axis.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def kv_quant(x: jax.Array):
    """[..., D] -> (int8 codes [..., D], f32 scales [..., 1]):
    symmetric per-vector quantization over the feature axis."""
    a = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    s = jnp.maximum(a, 1e-12) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / s), -127, 127)
    return q.astype(jnp.int8), s.astype(jnp.float32)


def kv_dequant(q: jax.Array, s: jax.Array, dtype) -> jax.Array:
    """Reconstruct [..., D] in compute dtype. NOT on the decode hot
    path (see module docstring — materializing this tensor was the
    0.73x regression); kept as the scheme's reference reconstruction
    for tests and offline use."""
    return (q.astype(jnp.float32) * s).astype(dtype)
