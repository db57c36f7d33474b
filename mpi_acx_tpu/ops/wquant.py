"""Int8 weight-only quantization for serving.

Decode is HBM-bandwidth-bound on RE-READING THE WEIGHTS every token
(at B=8/GPT-2-125M the weight stream is ~40x the KV stream), so halving weight bytes — bf16 -> int8 + one f32
scale per output channel — roughly doubles the bandwidth roofline at a
small, measured quality cost. This is the serving-side counterpart of
the int8 gradient ring (parallel/quantized.py): same symmetric
per-block scheme, applied to the static weights instead of the wire.

Design: :func:`quantize_weights_int8` keeps the parameter pytree's
SHAPE — each quantized leaf w is replaced by its int8 quantization and
a broadcast-ready ``w + "_scale"`` companion leaf is added beside it.
The model blocks read every matmul weight through :func:`wread`, which
transparently dequantizes when a scale is present (XLA fuses the
int8->bf16 convert + multiply into the matmul's operand read, so HBM
traffic is the int8 bytes). Unquantized checkpoints hit the
``scale is None`` fast path, which is exactly the old
``lp[name].astype(dtype)``.

The embedding / unembedding stay bf16: the tied logits matmul sets
output quality directly and is one tensor, not a per-layer stream.

Supported entry points: the single-device serving stack — forward /
prefill / decode_step / generate for GPT-2 and Llama, speculative
decoding over them — AND tensor-parallel serving for both dense
families (plain and speculative): the TP shard fns re-layout each
``_scale`` companion alongside its weight, the spec trees gain
matching entries, and the TP layer ops read through :func:`wread`
(tp_inference). The MoE expert einsums have no wread path and REJECT
quantized expert weights loudly (moe_transformer
._reject_quantized_experts) rather than cast raw int8 codes without
their scales; MoE *attention* weights may be quantized (they ride the
shared GPT-2 ops).

The reference has no inference stack at all (SURVEY.md SS0); this
module exists for the framework goal's serving-perf axis.
"""

from __future__ import annotations

from typing import Any, Dict, Iterable

import jax
import jax.numpy as jnp

# Per-family matmul weights worth streaming as int8 (contracted axis is
# second-to-last at every call site: y = x @ w).
GPT2_WEIGHTS = ("wqkv", "wo", "w1", "w2")
LLAMA_WEIGHTS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def wread(lp: Dict[str, Any], name: str, dtype) -> jax.Array:
    """Read matmul weight ``name`` in compute ``dtype``, transparently
    dequantizing int8 weight-only quantization (``name + "_scale"``
    present -> q * s). The one read path every block uses, so a
    quantized and an unquantized checkpoint run the same code."""
    w = lp[name]
    s = lp.get(name + "_scale")
    if s is None:
        return w.astype(dtype)
    # Dequantize in f32 (the scale's dtype) BEFORE casting to compute
    # dtype: a bf16 scale would add ~0.4% error on top of the int8
    # rounding. XLA fuses convert+mul into the matmul's operand read.
    return (w.astype(jnp.float32) * s).astype(dtype)


def _quant_leaf(w: jax.Array):
    """Symmetric per-output-channel int8: scale = amax over the
    CONTRACTED axis (second-to-last; every call site computes x @ w),
    keepdims so the companion broadcasts in ``wread`` unchanged."""
    a = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=-2, keepdims=True)
    s = jnp.maximum(a, 1e-12) / 127.0
    q = jnp.clip(jnp.round(w.astype(jnp.float32) / s), -127, 127)
    return q.astype(jnp.int8), s.astype(jnp.float32)


def quantize_weights_int8(params: Dict[str, Any],
                          names: Iterable[str]) -> Dict[str, Any]:
    """Quantize the named ``params["layers"]`` matmul weights to int8,
    adding ``<name>_scale`` companion leaves (leading layer axis
    preserved, so the decode layer scans carry them like any other
    leaf). Everything else — biases, norms, embeddings — is untouched.

    Use GPT2_WEIGHTS / LLAMA_WEIGHTS for ``names``, or any subset."""
    lay = dict(params["layers"])
    for name in names:
        q, s = _quant_leaf(lay[name])
        lay[name] = q
        lay[name + "_scale"] = s
    return dict(params, layers=lay)


def weight_bytes(params: Dict[str, Any]) -> int:
    """Total parameter bytes as stored — the numerator of the decode
    bandwidth roofline, so that an int8 model's roofline reflects the
    actual quantized stream."""
    return sum(x.size * x.dtype.itemsize
               for x in jax.tree.leaves(params))
