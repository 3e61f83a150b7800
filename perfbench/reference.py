"""The plain reference: Mistral-7B's decoder (RMSNorm, rotary
attention with grouped KV heads, SwiGLU MLP, LM head tied to the
embedding as the program runs it) in jax.numpy and float32, with every
matmul at the highest precision, and the configuration's optimizer
written out by hand. It imports nothing of the program.

`readings` drives three optimizer steps from the benchmark's weights and
token batches and returns what the correctness check compares: each
step's loss, the first gradient's norm per leaf, and each leaf's change
after the three steps. The same code with `precision="fp8"` is the
control: every matmul's operands rounded to float8 with one scale per
tensor (e4m3 forward; in the backward pass the cotangent in e5m2), the
nearest precision below the configuration's bfloat16.

The layers run in a scan, each rematerialised in the backward pass
(`jax.checkpoint`), so the reference holds one layer's activations at a
time besides its state.
"""

from __future__ import annotations

import json
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from perfbench import model

HIGHEST = jax.lax.Precision.HIGHEST
# the parameters and the optimizer's moment are kept in the configuration's
# dtype between steps, as the program keeps them; each step computes in
# float32 from the values so kept
STORE = jnp.bfloat16
E4M3_MAX, E5M2_MAX = 448.0, 57344.0


def _quantize(x, dtype, top: float):
    """x rounded to a float8 type, with one scale for the tensor."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _ein_fp8(spec, a, b):
    return jnp.einsum(spec, _quantize(a, jnp.float8_e4m3fn, E4M3_MAX),
                      _quantize(b, jnp.float8_e4m3fn, E4M3_MAX),
                      precision=HIGHEST)


def _ein_fp8_fwd(spec, a, b):
    qa = _quantize(a, jnp.float8_e4m3fn, E4M3_MAX)
    qb = _quantize(b, jnp.float8_e4m3fn, E4M3_MAX)
    return jnp.einsum(spec, qa, qb, precision=HIGHEST), (qa, qb)


def _ein_fp8_bwd(spec, res, ct):
    """Gradients from the float8 operands and the cotangent rounded to
    e5m2, as float8 training computes them."""
    qa, qb = res
    _, vjp = jax.vjp(lambda x, y: jnp.einsum(spec, x, y, precision=HIGHEST),
                     qa, qb)
    return vjp(_quantize(ct, jnp.float8_e5m2, E5M2_MAX))


_ein_fp8.defvjp(_ein_fp8_fwd, _ein_fp8_bwd)


def _ein(precision: str):
    if precision == "f32":
        return lambda spec, a, b: jnp.einsum(spec, a, b, precision=HIGHEST)
    if precision == "fp8":
        return _ein_fp8
    raise ValueError(f"unknown precision {precision!r}")


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rotate(x, cos, sin):
    """Rotary embedding, rotate_half convention (first half, second
    half), as Mistral's reference code applies it."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    c, s = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def loss_fn(params, tokens, w: dict, precision: str = "f32",
            keep_rows=None):
    """Mean next-token cross-entropy. keep_rows (a planted fault only)
    averages over the first rows of the batch alone."""
    ein = _ein(precision)
    if keep_rows is not None:
        tokens = tokens[:keep_rows]
    inp, tgt = tokens[:, :-1], tokens[:, 1:]
    B, S = inp.shape
    H, KV, D = w["heads"], w["kv_heads"], w["d"]
    hd = D // H
    inv = 1.0 / (w["theta"] ** (np.arange(0, hd, 2) / hd))
    ang = np.outer(np.arange(S), inv)
    cos, sin = jnp.asarray(np.cos(ang), jnp.float32), jnp.asarray(
        np.sin(ang), jnp.float32)
    causal = jnp.tril(jnp.ones((S, S), bool))

    def layer(x, lp):
        h = _rms_norm(x, lp["attn_norm"], w["eps"])
        q = ein("bsd,de->bse", h, lp["wq"]).reshape(B, S, H, hd)
        k = ein("bsd,de->bse", h, lp["wk"]).reshape(B, S, KV, hd)
        v = ein("bsd,de->bse", h, lp["wv"]).reshape(B, S, KV, hd)
        q, k = _rotate(q, cos, sin), _rotate(k, cos, sin)
        k = jnp.repeat(k, H // KV, axis=2)
        v = jnp.repeat(v, H // KV, axis=2)
        scores = ein("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
        scores = jnp.where(causal[None, None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        attn = ein("bhqk,bkhd->bqhd", probs, v).reshape(B, S, H * hd)
        x = x + ein("bse,ed->bsd", attn, lp["wo"])
        h = _rms_norm(x, lp["mlp_norm"], w["eps"])
        gate = jax.nn.silu(ein("bsd,df->bsf", h, lp["w_gate"]))
        up = ein("bsd,df->bsf", h, lp["w_up"])
        return x + ein("bsf,fd->bsd", gate * up, lp["w_down"])

    x = params["tok_embed"][inp]
    x, _ = jax.lax.scan(jax.checkpoint(lambda x, lp: (layer(x, lp), None)),
                        x, params["layers"])
    x = _rms_norm(x, params["final_norm"], w["eps"])
    logits = ein("bsd,vd->bsv", x, params["tok_embed"])
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, tgt[..., None], -1))


def _update(opt: dict, params, mu, grads):
    """One step of Lion (Chen et al. 2023), written out."""
    if opt["name"] != "lion":
        raise ValueError(f"unknown optimizer {opt['name']!r}")
    b1, b2, lr = opt["b1"], opt["b2"], opt["lr"]
    params = jax.tree.map(
        lambda p, m, g: p - lr * jnp.sign((1 - b1) * g + b1 * m),
        params, mu, grads)
    mu = jax.tree.map(lambda m, g: b2 * m + (1 - b2) * g, mu, grads)
    return params, mu


def leaf_norms(tree) -> dict:
    """{leaf name: float32 L2 norm}, on the device."""
    return {name: jnp.sqrt(jnp.sum(jnp.square(leaf.astype(jnp.float32))))
            for name, leaf in model.flat_leaves(tree)}


def step_fn(conf: dict, precision: str = "f32", keep_rows=None):
    """The reference's jitted step: (params, moment, tokens) kept in
    STORE -> the next (params, moment), the loss, and the gradient's
    norm per leaf."""
    return model.memo(("reference", json.dumps(conf, sort_keys=True),
                       precision, keep_rows),
                      lambda: _step_fn(conf, precision, keep_rows))


def _step_fn(conf: dict, precision: str, keep_rows):
    w = model.widths(conf)
    opt = conf["training"]["optimizer"]

    def step(params, mu, tokens):
        f32 = partial(jax.tree.map, lambda a: a.astype(jnp.float32))
        loss, grads = jax.value_and_grad(loss_fn)(
            f32(params), tokens, w, precision, keep_rows)
        new_p, new_mu = _update(opt, f32(params), f32(mu), grads)
        keep = partial(jax.tree.map, lambda a: a.astype(STORE))
        return keep(new_p), keep(new_mu), loss, leaf_norms(grads)

    return jax.jit(step, donate_argnums=(0, 1))


def readings(conf: dict, seed: int, batches: list, precision: str = "f32",
             keep_rows=None, sharding=None) -> dict:
    """Three optimizer steps from the benchmark's weights for `seed`, on
    batches[0..2]: {"loss": [3 floats], "grad": {leaf: norm of the first
    gradient}, "update": {leaf: norm of the change after three steps}}.

    sharding (several chips): a function from a leaf's shape to the
    NamedSharding the leaf is kept with."""
    w = model.widths(conf)
    out_sh = None
    if sharding is not None:
        out_sh = jax.tree.map(sharding, model.leaf_shapes(w),
                              is_leaf=lambda x: isinstance(x, tuple))
    params = model.make_weights(w, seed, out_sh)
    mu = jax.jit(partial(jax.tree.map, jnp.zeros_like))(params)

    step = step_fn(conf, precision, keep_rows)
    losses, grad = [], None
    for t in range(3):
        params, mu, loss, gnorms = step(params, mu, batches[t])
        losses.append(float(loss))
        if t == 0:
            grad = {k: float(v) for k, v in gnorms.items()}
    del mu

    # the initial weights again, from the executable that made them
    upd, moved = change_norms(params, model.make_weights(w, seed, out_sh))
    return {"loss": losses, "grad": grad,
            "update": {k: float(v) for k, v in upd.items()},
            "moved_share": {k: float(v) for k, v in moved.items()}}


@jax.jit
def change_norms(p, p0):
    """Each leaf's norm of p - p0, and the share of its elements that
    changed at all."""
    moved = {k: jnp.mean((a != b).astype(jnp.float32))
             for (k, a), (_, b) in zip(model.flat_leaves(p),
                                       model.flat_leaves(p0))}
    return leaf_norms(jax.tree.map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
        p, p0)), moved


def readings_on(conf: dict, seed: int, devices: list, **kw) -> dict:
    """`readings` on these devices: on several chips each float32 leaf
    is split along its last axis (every leaf's size divides by the
    chips) and the tokens are copied to each."""
    w = model.widths(conf)
    sharding = rep = None
    if len(devices) > 1:
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        mesh = Mesh(np.asarray(devices), ("x",))
        rep = NamedSharding(mesh, P())

        def sharding(shape):
            return NamedSharding(mesh, P(*([None] * (len(shape) - 1)), "x"))
    return readings(conf, seed, model.token_pool(w, seed, 3, rep),
                    sharding=sharding, **kw)


def gaps(ref: dict, got: dict) -> dict:
    """The numbers `correct` compares, from two sets of readings.

    loss_gap: the largest of the three steps' |loss - reference loss|.
    grad_gap, update_gap: the worst leaf's gap between the two norms,
    measured against the reference's norm of that leaf or of the median
    leaf, whichever is larger. Leaves whose first reference gradient is
    under a thousandth of the median leaf's move by round-off alone and
    are left out of update_gap."""
    loss_gap = max(abs(a - b) for a, b in zip(got["loss"], ref["loss"]))

    def worst(kind: str, leaves) -> tuple[float, str]:
        med = float(np.median([ref[kind][k] for k in ref[kind]]))
        return max((abs(got[kind][k] - ref[kind][k])
                    / max(ref[kind][k], med, 1e-30), k) for k in leaves)

    gmed = float(np.median(list(ref["grad"].values())))
    moving = [k for k in ref["update"] if ref["grad"][k] >= 1e-3 * gmed]
    grad_gap, grad_leaf = worst("grad", list(ref["grad"]))
    upd_gap, upd_leaf = worst("update", moving)
    return {"loss_gap": loss_gap, "grad_gap": grad_gap,
            "grad_gap_leaf": grad_leaf, "update_gap": upd_gap,
            "update_gap_leaf": upd_leaf,
            "update_leaves_left_out": sorted(set(ref["update"]) -
                                             set(moving))}
