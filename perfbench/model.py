"""What the benchmark makes from a configuration file and a seed: the
weights, the token feed, the optimizer, and the FLOPs of a step.

The weights and tokens are the benchmark's own (the same seed gives the
same arrays); the program receives them as inputs. Nothing here imports
the program except `program_config`, which states the configuration in
the program's terms, and `program_optimizer`, which builds the optax
optimizer the job hands to the program's train step.
"""

from __future__ import annotations

import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_json(rel_or_abs: str) -> dict:
    path = rel_or_abs if os.path.isabs(rel_or_abs) else os.path.join(
        ROOT, rel_or_abs)
    with open(path) as f:
        return json.load(f)


def widths(conf: dict) -> dict:
    """The sizes a step needs, from a configuration file."""
    return {"vocab": conf["vocab_size"], "d": conf["hidden_size"],
            "layers": conf["num_hidden_layers"],
            "heads": conf["num_attention_heads"],
            "kv_heads": conf["num_key_value_heads"],
            "ff": conf["intermediate_size"],
            "theta": float(conf["rope_theta"]),
            "eps": float(conf["rms_norm_eps"]),
            "max_pos": conf["max_position_embeddings"],
            "init_std": conf["initializer_range"],
            "batch": conf["training"]["batch"],
            "seq": conf["training"]["seq"]}


def check_departures(conf: dict) -> None:
    """Refuse a configuration the program would not run as stated."""
    w = widths(conf)
    if conf.get("sliding_window") and conf["sliding_window"] < w["seq"]:
        raise ValueError("sliding window shorter than the sequence: the "
                         "program's full causal attention is not exact")
    if conf.get("hidden_act") != "silu":
        raise ValueError("the program's MLP is SwiGLU (silu)")
    if not conf.get("tie_word_embeddings"):
        raise ValueError("the program ties the LM head to the embedding")


def program_config(conf: dict):
    import jax.numpy as jnp

    from deepflow_tpu.models.llama import LlamaConfig
    w = widths(conf)
    return LlamaConfig(vocab=w["vocab"], d_model=w["d"],
                       n_layers=w["layers"], n_heads=w["heads"],
                       n_kv_heads=w["kv_heads"], d_ff=w["ff"],
                       max_seq=max(w["max_pos"], w["seq"]),
                       rope_theta=w["theta"], norm_eps=w["eps"],
                       dtype=jnp.bfloat16)


def program_optimizer(conf: dict):
    import optax
    o = conf["training"]["optimizer"]
    if o["name"] != "lion":
        raise ValueError(f"unknown optimizer {o['name']!r}")
    return optax.lion(o["lr"], b1=o["b1"], b2=o["b2"], weight_decay=0.0)


def first_grad_from_state(conf: dict, opt_state):
    """The first step's gradient as the optimizer got it, worked out
    from its state after that one step: Lion's moment is then
    (1 - b2) * g."""
    import jax
    b2 = conf["training"]["optimizer"]["b2"]
    return jax.tree.map(lambda m: m / (1.0 - b2), opt_state[0].mu)


def leaf_shapes(w: dict) -> dict:
    """The program's parameter tree (models/llama.py), as shapes."""
    hd = w["d"] // w["heads"]
    L, D, F, V = w["layers"], w["d"], w["ff"], w["vocab"]
    return {"tok_embed": (V, D),
            "layers": {"attn_norm": (L, D),
                       "wq": (L, D, w["heads"] * hd),
                       "wk": (L, D, w["kv_heads"] * hd),
                       "wv": (L, D, w["kv_heads"] * hd),
                       "wo": (L, w["heads"] * hd, D),
                       "mlp_norm": (L, D),
                       "w_gate": (L, D, F), "w_up": (L, D, F),
                       "w_down": (L, F, D)},
            "final_norm": (D,)}


_JITTED: dict = {}


def memo(key, build):
    """One jitted function per key in this process: the runs of several
    seeds in one process compile once."""
    if key not in _JITTED:
        _JITTED[key] = build()
    return _JITTED[key]


def make_weights_fn(w: dict, out_shardings=None):
    """One jitted call: seed -> the whole bf16 parameter tree, on the
    device. Dense leaves are normal(0, initializer_range), norms ones."""
    return memo(("weights", json.dumps(w, sort_keys=True),
                 repr(out_shardings)),
                lambda: _make_weights_fn(w, out_shardings))


def _make_weights_fn(w: dict, out_shardings=None):
    import jax
    import jax.numpy as jnp

    named = flat_leaves(leaf_shapes(w))

    def init(seed_lo, seed_hi):
        key = jax.random.fold_in(jax.random.key(seed_lo), seed_hi)
        keys = jax.random.split(key, len(named))
        out: dict = {}
        for k, (name, shape) in zip(keys, named):
            if name.endswith("norm"):
                leaf = jnp.ones(shape, jnp.bfloat16)
            else:
                leaf = (w["init_std"] * jax.random.normal(
                    k, shape, jnp.float32)).astype(jnp.bfloat16)
            node = out
            *parents, last = name.split("/")
            for part in parents:
                node = node.setdefault(part, {})
            node[last] = leaf
        return out

    return jax.jit(init, out_shardings=out_shardings)


def flat_leaves(tree: dict, prefix: str = "") -> list:
    """[(name, leaf)] of a nested dict, names joined by '/', sorted."""
    out = []
    for k in sorted(tree):
        v = tree[k]
        name = f"{prefix}{k}"
        if isinstance(v, dict):
            out.extend(flat_leaves(v, name + "/"))
        else:
            out.append((name, v))
    return out


def seed_parts(seed: int) -> tuple[int, int]:
    return seed % 2**32, (seed // 2**32) % 2**31


def make_weights(w: dict, seed: int, out_shardings=None):
    import numpy as np
    lo, hi = seed_parts(seed)
    return make_weights_fn(w, out_shardings)(np.uint32(lo), np.int32(hi))


def token_pool(w: dict, seed: int, n: int, sharding=None) -> list:
    """n distinct batches of (batch, seq + 1) token ids, made on the
    device in one call; the window cycles through them."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    def pool(seed_lo, seed_hi):
        key = jax.random.fold_in(jax.random.fold_in(
            jax.random.key(seed_lo), seed_hi), 1)
        toks = jax.random.randint(key, (n, w["batch"], w["seq"] + 1), 0,
                                  w["vocab"], jnp.int32)
        return tuple(toks[i] for i in range(n))

    lo, hi = seed_parts(seed)
    return list(jax.jit(pool, out_shardings=sharding)(
        np.uint32(lo), np.int32(hi)))


def flops_per_step(w: dict) -> float:
    """Model FLOPs of one training step (forward + backward), counted as
    PaLM's appendix B does: 6 per parameter per token, the LM head
    included once (tied to the embedding; the embedding lookup is no
    matmul), plus 12 * layers * d_model * seq per token for attention.
    Recomputed operations do not count."""
    shapes = leaf_shapes(w)
    matmul = sum(_prod(s) for k, s in shapes["layers"].items()
                 if not k.endswith("norm"))
    matmul += _prod(shapes["tok_embed"])                 # the LM head
    tokens = w["batch"] * w["seq"]
    return tokens * (6.0 * matmul + 12.0 * w["layers"] * w["d"] * w["seq"])


def _prod(shape) -> int:
    out = 1
    for s in shape:
        out *= s
    return out
