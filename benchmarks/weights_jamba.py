"""Jamba weights from ``--seed``, made by the benchmark, on the device,
in the type they are used in, never whole in float32 (a large leaf is
drawn a matrix at a time: ``weights_lfm2._leaf``).

The tree has the layout the program's ``jamba`` family reads: layers
grouped into STRETCHES of whole periods (:func:`stretches`; the
program's ``kvpage.compress_layers`` finds the same grouping by the same
rule, written there by itself), ``tree["seg<i>"]`` one dict of leaves
``[repeats, ...]`` a layer of the period (the dict itself when the
period is one layer, else a tuple of them), ``embed`` tied to the head,
``final_norm``. :func:`plan` lists where each layer's leaves lie, which
is what the plain reference (``reference/jamba.py``) is handed.

Leaves (channels C = ``mamba_expand * hidden_size`` LAST wherever a leaf
meets the state): ``norm1``, ``norm2`` [d]; ``w_gate``, ``w_up`` [d,
ff], ``w_down`` [ff, d]; attention ``wq`` [d, Hq*Dh], ``wk``, ``wv`` [d,
Hkv*Dh], ``wo`` [Hq*Dh, d]; Mamba ``w_in`` [d, 2C] (columns u | z),
``conv_w`` [taps, C] (row j meets u at t - (taps - 1) + j), ``conv_b``
[C], ``w_x`` [C, R + 2N] (columns dt_r | B | C), ``dt_norm`` [R],
``b_norm``, ``c_norm`` [N], ``w_dt`` [R, C], ``b_dt`` [C], ``A_log`` [N,
C], ``D`` [C], ``w_out`` [C, d]; ``b_dt``, ``A_log`` and ``D`` float32.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmarks.weights import seed_key
from benchmarks.weights_lfm2 import _leaf


def layer_kinds(c: dict) -> list:
    """"attention" | "mamba" a layer: HF's rule, ``i % attn_layer_period
    == attn_layer_offset`` (the catalog gives no order)."""
    return ["attention" if i % c["attn_layer_period"] == c["attn_layer_offset"]
            else "mamba" for i in range(c["num_hidden_layers"])]


def stretches(c: dict) -> list:
    """[(key, period [kind], repeats)]: at each point the period whose
    repeats cover most layers, the shortest of equals."""
    kinds, out, at = layer_kinds(c), [], 0
    while at < len(kinds):
        p, r = 1, 1
        for q in range(1, (len(kinds) - at) // 2 + 1):
            n = 1
            while kinds[at + n * q:at + (n + 1) * q] == kinds[at:at + q]:
                n += 1
            if n > 1 and n * q > p * r:
                p, r = q, n
        out.append((f"seg{len(out)}", kinds[at:at + p], r))
        at += p * r
    return out


def plan(c: dict) -> tuple:
    """((kind, key, place in the period or None, repeat), ...) a layer,
    in model order: layer l's leaf ``name`` is
    ``tree[key][place][name][repeat]`` (``tree[key][name][repeat]``
    where place is None)."""
    return tuple((kind, key, j if len(period) > 1 else None, r)
                 for key, period, repeats in stretches(c)
                 for r in range(repeats) for j, kind in enumerate(period))


def layer_shapes(c: dict, kind: str) -> dict:
    """Leaf name -> (shape, init: None = ones, "A" = log(1..N) a channel,
    "dt" = the bias whose softplus is log-uniform in 0.001-0.1 (Mamba's
    init), else a normal's scale: 0.02, or the file's ``init_scale``)."""
    d, ff, s = c["hidden_size"], c["intermediate_size"], c.get("init_scale",
                                                               0.02)
    out = {"norm1": ((d,), None), "norm2": ((d,), None),
           "w_gate": ((d, ff), s), "w_up": ((d, ff), s), "w_down": ((ff, d), s)}
    if kind == "attention":
        dh = d // c["num_attention_heads"]
        hq, hkv = c["num_attention_heads"] * dh, c["num_key_value_heads"] * dh
        out.update(wq=((d, hq), s), wk=((d, hkv), s), wv=((d, hkv), s),
                   wo=((hq, d), s))
    else:
        ch, n = c["mamba_expand"] * d, c["mamba_d_state"]
        r = c["mamba_dt_rank"]
        out.update(w_in=((d, 2 * ch), s), conv_w=((c["mamba_d_conv"], ch), s),
                   conv_b=((ch,), s), w_x=((ch, r + 2 * n), s),
                   dt_norm=((r,), None), b_norm=((n,), None),
                   c_norm=((n,), None), w_dt=((r, ch), s), b_dt=((ch,), "dt"),
                   A_log=((n, ch), "A"), D=((ch,), "D"), w_out=((ch, d), s))
    return out


def n_params(c: dict) -> int:
    n = c["vocab_size"] * c["hidden_size"] + c["hidden_size"]
    for _, period, repeats in stretches(c):
        for kind in period:
            n += repeats * sum(math.prod(shape) for shape, _ in
                               layer_shapes(c, kind).values())
    return n


def _scan_leaf(key, shape, init):
    """The scan's own parameters, float32."""
    if init == "D":
        return jnp.ones(shape, jnp.float32)
    if init == "A":
        n = jnp.arange(1, shape[-2] + 1, dtype=jnp.float32)
        return jnp.broadcast_to(jnp.log(n)[:, None], shape) + 0.0
    lo, hi = math.log(0.001), math.log(0.1)
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32) * (hi - lo) + lo)
    return dt + jnp.log(-jnp.expm1(-dt))            # softplus's inverse


def make_jamba(c: dict, seed: int, dtype):
    """The parameter tree of configuration ``c`` in ``dtype``."""
    key = seed_key(seed)
    n = 0

    def leaf(shape, init):
        nonlocal n
        n += 1
        k = jax.random.fold_in(key, n)
        if init in ("A", "D", "dt"):
            return _scan_leaf(k, shape, init)
        return _leaf(k, shape, init, dtype)

    tree = {"embed": leaf((c["vocab_size"], c["hidden_size"]),
                          c.get("init_scale", 0.02)),
            "final_norm": leaf((c["hidden_size"],), None)}
    for seg, period, repeats in stretches(c):
        layers = [{name: leaf((repeats,) + shape, init) for name, (shape, init)
                   in sorted(layer_shapes(c, kind).items())}
                  for kind in period]
        tree[seg] = layers[0] if len(layers) == 1 else tuple(layers)
    return tree
