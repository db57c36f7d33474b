"""GigaChat3 (``deepseek_v3``) weights from ``--seed``, made by the
benchmark, on the device, in the type they are used in, never whole in
float32: a large leaf is drawn a ``[rows, columns]`` block at a time
(``lax.map``), so the 10 GB tree needs no second copy of any of it.

The tree has the layout the program's ``gigachat`` family reads:
``tree["seg0"]`` the leading dense layers and ``tree["seg1"]`` the expert
layers, each one dict of leaves ``[repeats, ...]``; ``embed`` [vocab,
d], an UNTIED ``head`` [d, vocab], ``final_norm``. :func:`plan` lists
where each layer's leaves lie, which is what the plain reference
(``reference/gigachat.py``) is handed: program and reference see the
same numbers and neither takes anything the other made.

Leaves a layer: ``attn_norm``, ``ffn_norm`` [d]; ``w_dq`` [d, q_rank],
``q_norm`` [q_rank], ``w_uq`` [q_rank, H * (nope + rope)] (a head's
columns ``[nope | rope]``), ``w_dkv`` [d, kv_rank + rope] (columns ``[c |
k_r]``), ``kv_norm`` [kv_rank], ``w_uk`` [kv_rank, H * nope], ``w_uv``
[kv_rank, H * v] (HF's ``kv_b_proj`` in two leaves), ``w_o`` [H * v, d];
dense FFN ``w1``, ``w3`` [d, ff], ``w2`` [ff, d]; expert layers ``gate``
[d, E] and ``bias`` [E] (float32: the router's published WIDTH E,
whatever is held), ``w1``, ``w3`` [held, d, f], ``w2`` [held, f, d] (the
experts ``first .. first + held - 1``), and the shared expert ``ws1``,
``ws3`` [d, f], ``ws2`` [f, d].

Assumed (the catalog row gives no initialisation): ``normal(0, 0.02)``
(or the file's ``init_scale``), norm weights 1, ``bias`` (HF's
``e_score_correction_bias``, zeros there) uniform +-:data:`BIAS`, as
LFM2's selection bias was assumed (ISSUE 40): not zero, so that selection
(``s + b``) and weight (``s``) differ (the best 8 of 128 scores lie
0.005-0.02 apart). The bias is drawn from the CONFIGURATION's own
``selection_bias_seed`` and not from ``--seed``, as a burst's multiset of
lengths is drawn from the traffic file's ``pair_seed``: +-0.05 on a
sigmoid score near 0.96 is +-1.3 of the router's logit (sd 1.69), so the
bias decides which experts are popular, and drawn from ``--seed`` this
chip's 16 of 256 experts took 5.0-7.5% of the routed pairs (sd 10%), kept
7.8-9.2 of 16 experts live a decode step, and ``serve_tok_s`` followed the
seed by 1.7% (PERF.md, PR 40): no seed may get other work than another.
Every other leaf is the seed's.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmarks.weights import seed_key

BIAS = 0.05         # the selection bias is uniform in +-BIAS


def held(c: dict) -> tuple:
    """(first, count, the router's width) of the experts held here."""
    e = c.get("experts_held", {})
    count = e.get("count", c["n_routed_experts"])
    return e.get("first", 0), count, e.get("of", count)


def stretches(c: dict) -> list:
    """[(key, ffn kind, repeats)]: the dense layers, then the expert
    layers (the program's ``kvpage.compress_layers`` finds the same)."""
    dense = c["first_k_dense_replace"]
    out = [("dense", dense), ("moe", c["num_hidden_layers"] - dense)]
    return [(f"seg{i}", ffn, n) for i, (ffn, n) in
            enumerate((f, n) for f, n in out if n)]


def plan(c: dict) -> tuple:
    """((ffn kind, key, repeat), ...) a layer, in model order: layer l's
    leaf ``name`` is ``tree[key][name][repeat]``."""
    return tuple((ffn, key, r) for key, ffn, n in stretches(c)
                 for r in range(n))


def layer_shapes(c: dict, ffn: str) -> dict:
    """Leaf name -> (shape, init: None = ones, "bias" = uniform +-BIAS
    in float32, else a normal's scale)."""
    d, H, s = c["hidden_size"], c["num_attention_heads"], \
        c.get("init_scale", 0.02)
    qr, kr = c["q_lora_rank"], c["kv_lora_rank"]
    nope, rope, v = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                     c["v_head_dim"])
    out = {"attn_norm": ((d,), None), "ffn_norm": ((d,), None),
           "w_dq": ((d, qr), s), "q_norm": ((qr,), None),
           "w_uq": ((qr, H * (nope + rope)), s),
           "w_dkv": ((d, kr + rope), s), "kv_norm": ((kr,), None),
           "w_uk": ((kr, H * nope), s), "w_uv": ((kr, H * v), s),
           "w_o": ((H * v, d), s)}
    if ffn == "dense":
        ff = c["intermediate_size"]
        out.update(w1=((d, ff), s), w3=((d, ff), s), w2=((ff, d), s))
    else:
        f = c["moe_intermediate_size"]
        _, n, width = held(c)
        out.update(gate=((d, width), s), bias=((width,), "bias"),
                   w1=((n, d, f), s), w3=((n, d, f), s), w2=((n, f, d), s),
                   ws1=((d, f), s), ws3=((d, f), s), ws2=((f, d), s))
    return out


def n_params(c: dict) -> int:
    n = 2 * c["vocab_size"] * c["hidden_size"] + c["hidden_size"]
    for _, ffn, repeats in stretches(c):
        n += repeats * sum(math.prod(shape) for shape, _ in
                           layer_shapes(c, ffn).values())
    return n


def _leaf(key, shape, init, dtype):
    if init is None:
        return jnp.ones(shape, dtype)
    if init == "bias":
        return jax.random.uniform(key, shape, jnp.float32, -BIAS, BIAS)

    def draw(k, sh):
        return (jax.random.normal(k, sh, jnp.float32) * init).astype(dtype)
    if math.prod(shape) < (1 << 24):
        return jax.jit(draw, static_argnums=1)(key, shape)
    # blocks of rows of the last matrix: the most that divide its rows
    # and stay under 2**25 values (128 MB in float32)
    rows = next(r for r in range(shape[-2], 0, -1)
                if shape[-2] % r == 0 and r * shape[-1] <= (1 << 25))
    lead = math.prod(shape[:-2]) * (shape[-2] // rows)
    keys = jax.random.split(key, lead)
    out = jax.jit(lambda ks: jax.lax.map(
        lambda k: draw(k, (rows, shape[-1])), ks))(keys)
    return out.reshape(shape)


def make_gigachat(c: dict, seed: int, dtype):
    """The parameter tree of configuration ``c`` in ``dtype``: from
    ``seed``, but for the selection bias, which is the configuration's
    (module docstring)."""
    keys = {False: seed_key(seed), True: seed_key(c["selection_bias_seed"])}
    n = 0

    def leaf(shape, init):
        nonlocal n
        n += 1
        return _leaf(jax.random.fold_in(keys[init == "bias"], n), shape,
                     init, dtype)

    s = c.get("init_scale", 0.02)
    tree = {"embed": leaf((c["vocab_size"], c["hidden_size"]), s),
            "head": leaf((c["hidden_size"], c["vocab_size"]), s),
            "final_norm": leaf((c["hidden_size"],), None)}
    for seg, ffn, repeats in stretches(c):
        tree[seg] = {name: leaf((repeats,) + shape, init)
                     for name, (shape, init)
                     in sorted(layer_shapes(c, ffn).items())}
    return tree
