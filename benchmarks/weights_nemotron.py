"""Nemotron-H (``nemotron_h``) weights from ``--seed``, made by the
benchmark, on the device, in the type they are used in, never whole in
float32 (a large leaf is drawn a block at a time:
``weights_gigachat._leaf``).

The tree has the layout the program's ``nemotron_h`` family reads: the
layers of ``hybrid_override_pattern`` (``M`` Mamba-2, ``*`` attention,
``E`` experts) grouped into STRETCHES of whole periods (:func:`stretches`;
the program's ``kvpage.compress_layers`` finds the same grouping by the
same rule, written there by itself: ``MEMEMEM*EME`` -> ``(M E) x 3, M, *,
E, M, E``), ``tree["seg<i>"]`` one dict of leaves ``[repeats, ...]`` a
layer of the period (the dict itself when the period is one layer, else a
tuple of them), ``embed`` [vocab, d], an UNTIED ``head`` [d, vocab],
``final_norm``. :func:`plan` lists where each layer's leaves lie, which is
what the plain reference (``reference/nemotron_h.py``) is handed: program
and reference see the same numbers and neither takes anything the other
made.

Leaves (module docstring of ``mpi_acx_tpu/models/nemotron_h.py``):
``norm1`` [d] in every kind; ``M``: ``w_in`` [d, 2 C + 2 G N + H]
(columns z | x | B | C | dt), ``conv_w`` [taps, C + 2 G N] (row j meets
the input at t - (taps - 1) + j), ``conv_b``, ``dt_bias``, ``A_log``,
``D`` [H] (float32), ``mix_norm`` [C], ``w_out`` [C, d]; ``*``: ``wq``,
``wk``, ``wv``, ``wo``; ``E``: ``gate`` [d, E] and ``bias`` [E]
(float32: the router's published WIDTH, whatever is held), ``w_dn`` [d,
latent], ``w1`` [held, latent, f], ``w2`` [held, f, latent], ``w_up``
[latent, d], ``ws1`` [d, fs], ``ws2`` [fs, d].

Assumed (the catalog row gives no initialisation; the configuration's
``assumed`` says the same): ``A_log = log(uniform[1, 16])`` a head,
``dt_bias`` the inverse softplus of a log-uniform draw in
``time_step_min .. time_step_max`` floored at ``time_step_floor`` (both
HF's ``NemotronHMamba2Mixer`` initialisation), ``D`` and norm weights 1,
``bias`` (HF's ``e_score_correction_bias``, zeros there) uniform
+-``weights_gigachat.BIAS`` from the CONFIGURATION's own
``selection_bias_seed`` and not from ``--seed`` (which experts are
popular, so how much this chip's 128 experts work, may not be a seed's
draw: PERF.md, PR 40), every other leaf ``normal(0, 0.02)`` (or the
file's ``init_scale``) from ``--seed``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmarks.flops_nemotron import mamba_dims
from benchmarks.weights import seed_key
from benchmarks.weights_gigachat import _leaf, held  # noqa: F401


def stretches(c: dict) -> list:
    """[(key, period [pattern letter], repeats)]: at each point the
    period whose repeats cover most layers, the shortest of equals."""
    kinds, out, at = list(c["hybrid_override_pattern"]), [], 0
    assert len(kinds) == c["num_hidden_layers"], (len(kinds), c[
        "num_hidden_layers"])
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
    """((pattern letter, key, place in the period or None, repeat), ...)
    a layer, in model order: layer l's leaf ``name`` is
    ``tree[key][place][name][repeat]`` (``tree[key][name][repeat]``
    where place is None)."""
    return tuple((kind, key, j if len(period) > 1 else None, r)
                 for key, period, repeats in stretches(c)
                 for r in range(repeats) for j, kind in enumerate(period))


def layer_shapes(c: dict, kind: str) -> dict:
    """Leaf name -> (shape, init: None = ones, "A", "dt", "D" the
    recurrence's own (float32), "bias" = uniform +-BIAS in float32, else
    a normal's scale)."""
    d, s = c["hidden_size"], c.get("init_scale", 0.02)
    out = {"norm1": ((d,), None)}
    if kind == "M":
        H, P, N, G = mamba_dims(c)
        conv = H * P + 2 * G * N                    # x, B and C
        out.update(w_in=((d, H * P + conv + H), s),
                   conv_w=((c["conv_kernel"], conv), s), conv_b=((conv,), s),
                   dt_bias=((H,), "dt"), A_log=((H,), "A"), D=((H,), "D"),
                   mix_norm=((H * P,), None), w_out=((H * P, d), s))
    elif kind == "*":
        hq = c["num_attention_heads"] * c["head_dim"]
        hkv = c["num_key_value_heads"] * c["head_dim"]
        out.update(wq=((d, hq), s), wk=((d, hkv), s), wv=((d, hkv), s),
                   wo=((hq, d), s))
    else:
        _, n, width = held(c)
        l, f = c["moe_latent_size"], c["moe_intermediate_size"]
        fs = c["moe_shared_expert_intermediate_size"]
        out.update(gate=((d, width), s), bias=((width,), "bias"),
                   w_dn=((d, l), s), w1=((n, l, f), s), w2=((n, f, l), s),
                   w_up=((l, d), s), ws1=((d, fs), s), ws2=((fs, d), s))
    return out


def n_params(c: dict) -> int:
    n = 2 * c["vocab_size"] * c["hidden_size"] + c["hidden_size"]
    for _, period, repeats in stretches(c):
        for kind in period:
            n += repeats * sum(math.prod(shape) for shape, _ in
                               layer_shapes(c, kind).values())
    return n


def _scan_leaf(key, shape, init, c):
    """The recurrence's own parameters, float32."""
    if init == "D":
        return jnp.ones(shape, jnp.float32)
    if init == "A":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    lo, hi = math.log(c["time_step_min"]), math.log(c["time_step_max"])
    dt = jnp.maximum(jnp.exp(jax.random.uniform(key, shape, jnp.float32)
                             * (hi - lo) + lo), c["time_step_floor"])
    return dt + jnp.log(-jnp.expm1(-dt))            # softplus's inverse


def make_nemotron(c: dict, seed: int, dtype):
    """The parameter tree of configuration ``c`` in ``dtype``: from
    ``seed``, but for the selection bias, which is the configuration's
    (module docstring)."""
    keys = {False: seed_key(seed), True: seed_key(c["selection_bias_seed"])}
    n = 0

    def leaf(shape, init):
        nonlocal n
        n += 1
        k = jax.random.fold_in(keys[init == "bias"], n)
        if init in ("A", "D", "dt"):
            return _scan_leaf(k, shape, init, c)
        return _leaf(k, shape, init, dtype)

    s = c.get("init_scale", 0.02)
    tree = {"embed": leaf((c["vocab_size"], c["hidden_size"]), s),
            "head": leaf((c["hidden_size"], c["vocab_size"]), s),
            "final_norm": leaf((c["hidden_size"],), None)}
    for seg, period, repeats in stretches(c):
        layers = [{name: leaf((repeats,) + shape, init) for name, (shape, init)
                   in sorted(layer_shapes(c, kind).items())}
                  for kind in period]
        tree[seg] = layers[0] if len(layers) == 1 else tuple(layers)
    return tree
