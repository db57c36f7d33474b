"""LFM2-MoE weights from ``--seed``, made by the benchmark, on the
device, in the type they are used in, never whole in float32: a large
leaf is drawn a ``[rows, columns]`` matrix at a time (``lax.map``), so
the 10 GB tree needs no second copy of any of it.

The tree has the layout the program's ``lfm2`` family reads: layers
grouped into STRETCHES of whole periods (:func:`stretches`; the
program's ``kvpage.compress_layers`` finds the same grouping by the
same rule, written there by itself), ``tree["seg<i>"]`` one dict of
leaves ``[repeats, ...]`` a layer of the period (the dict itself when
the period is one layer, else a tuple of them), ``embed`` tied to the
head, ``final_norm``. :func:`plan` lists where each layer's leaves lie,
which is what the plain reference (``reference/lfm2.py``) is handed:
program and reference see the same numbers and neither takes anything
the other made. A program that stops reading this layout fails at the
first call, loudly.

Leaves: ``op_norm``, ``ffn_norm`` [d]; attention ``wq`` [d, Hq*Dh],
``wk``, ``wv`` [d, Hkv*Dh], ``wo`` [Hq*Dh, d], ``q_norm``, ``k_norm``
[Dh]; conv ``w_in`` [d, 3d] (columns B | C | X), ``conv_w`` [d, taps]
(column j meets z at t - (taps - 1) + j), ``w_out`` [d, d]; dense FFN
``w1``, ``w3`` [d, ff], ``w2`` [ff, d]; experts ``gate`` [d, E],
``bias`` [E] (float32: it only selects), ``w1``, ``w3`` [held, d, f],
``w2`` [held, f, d].
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmarks.weights import seed_key


def layer_kinds(c: dict) -> list:
    """[(operator, ffn)] a layer: ("conv" | "full_attention", "dense" |
    "moe")."""
    return [(t, "dense" if l < c["num_dense_layers"] else "moe")
            for l, t in enumerate(c["layer_types"])]


def stretches(c: dict) -> list:
    """[(key, period [(operator, ffn)], repeats)]: at each point the
    period whose repeats cover most layers, the shortest of equals."""
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
    """((operator, ffn, key, place in the period or None, repeat), ...)
    a layer, in model order: layer l's leaf ``name`` is
    ``tree[key][place][name][repeat]`` (``tree[key][name][repeat]``
    where place is None)."""
    out = []
    for key, period, repeats in stretches(c):
        for r in range(repeats):
            for j, (op, ffn) in enumerate(period):
                out.append((op, ffn, key, j if len(period) > 1 else None, r))
    return tuple(out)


def layer_shapes(c: dict, op: str, ffn: str) -> dict:
    """Leaf name -> (shape, init: None = ones, "bias" = uniform
    +-0.05 in float32, else a normal's scale: 0.02, or the file's
    ``init_scale``, which a tiny test configuration raises so that its
    layers, and not the tied embedding's echo, decide the logits)."""
    d, s = c["hidden_size"], c.get("init_scale", 0.02)
    dh = d // c["num_attention_heads"]
    out = {"op_norm": ((d,), None), "ffn_norm": ((d,), None)}
    if op == "full_attention":
        hq, hkv = c["num_attention_heads"], c["num_key_value_heads"]
        out.update(wq=((d, hq * dh), s), wk=((d, hkv * dh), s),
                   wv=((d, hkv * dh), s), wo=((hq * dh, d), s),
                   q_norm=((dh,), None), k_norm=((dh,), None))
    else:
        out.update(w_in=((d, 3 * d), s), conv_w=((d, c["conv_L_cache"]), s),
                   w_out=((d, d), s))
    if ffn == "dense":
        ff = c["intermediate_size"]
        out.update(w1=((d, ff), s), w3=((d, ff), s), w2=((ff, d), s))
    else:
        e, f = c["num_experts"], c["moe_intermediate_size"]
        held = c.get("experts_held", {}).get("count", e)
        out.update(gate=((d, e), s), bias=((e,), "bias"),
                   w1=((held, d, f), s), w3=((held, d, f), s),
                   w2=((held, f, d), s))
    return out


def n_params(c: dict) -> int:
    n = c["vocab_size"] * c["hidden_size"] + c["hidden_size"]
    for _, period, repeats in stretches(c):
        for op, ffn in period:
            n += repeats * sum(math.prod(shape) for shape, _ in
                               layer_shapes(c, op, ffn).values())
    return n


def _leaf(key, shape, init, dtype):
    if init is None:
        return jnp.ones(shape, dtype)
    if init == "bias":
        return jax.random.uniform(key, shape, jnp.float32, -0.05, 0.05)

    def draw(k, sh):
        return (jax.random.normal(k, sh, jnp.float32) * init).astype(dtype)
    if len(shape) < 3 and math.prod(shape) < (1 << 24):
        return jax.jit(draw, static_argnums=1)(key, shape)
    # a matrix (or a block of rows) at a time
    if len(shape) < 3:
        rows = 4096
        assert shape[0] % rows == 0, shape
        lead, cell = (shape[0] // rows,), (rows,) + shape[1:]
    else:
        lead, cell = shape[:-2], shape[-2:]
    keys = jax.random.split(key, math.prod(lead))
    out = jax.jit(lambda ks: jax.lax.map(lambda k: draw(k, cell), ks))(keys)
    return out.reshape(shape)


def make_lfm2(c: dict, seed: int, dtype):
    """The parameter tree of configuration ``c`` in ``dtype``."""
    key = seed_key(seed)
    n = 0

    def leaf(shape, init):
        nonlocal n
        n += 1
        return _leaf(jax.random.fold_in(key, n), shape, init, dtype)

    tree = {"embed": leaf((c["vocab_size"], c["hidden_size"]),
                          c.get("init_scale", 0.02)),
            "final_norm": leaf((c["hidden_size"],), None)}
    for seg, period, repeats in stretches(c):
        layers = [{name: leaf((repeats,) + shape, init) for name, (shape, init)
                   in sorted(layer_shapes(c, op, ffn).items())}
                  for op, ffn in period]
        tree[seg] = layers[0] if len(layers) == 1 else tuple(layers)
    return tree
