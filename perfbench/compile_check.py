"""Compile a configuration's programs for a described TPU v5e, with no
chip: the program's train step as the job runs it and the reference's
step, and print each one's memory_analysis() per device.

    JAX_PLATFORMS=cpu python perfbench/compile_check.py --config mistral7b-l4

The compiler refuses what would not fit or not partition. Nothing runs,
so this says nothing about results or times. The numbers it prints are
kept in the configuration's file under "memory_analysis".
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def _shapes(tree_shapes, sharding, dtype):
    import jax
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s, dtype,
                                                       sharding=sharding(s)),
                        tree_shapes, is_leaf=lambda x: isinstance(x, tuple))


def _analysis(compiled) -> dict:
    ma = compiled.memory_analysis()
    return {k: getattr(ma, f"{k}_size_in_bytes")
            for k in ("argument", "output", "alias", "temp")}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--config", required=True)
    args = parser.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    import optax
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P
    from jax.sharding import SingleDeviceSharding

    from deepflow_tpu.models.llama import make_train_step, param_specs
    from deepflow_tpu.parallel.mesh import make_mesh, named_sharding_tree
    from perfbench import model, reference
    jax.config.update("jax_enable_compilation_cache", False)
    conf = model.load_json(f"perfbench/configs/{args.config}.json")
    w = model.widths(conf)
    chips = conf["training"]["chips"]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    devices = list(topo.devices)[:chips]
    cfg = model.program_config(conf)
    train_step, init_opt = make_train_step(
        cfg, optimizer=model.program_optimizer(conf))
    shapes = model.leaf_shapes(w)
    if chips > 1:
        mesh = make_mesh(devices)
        specs = named_sharding_tree(param_specs(cfg), mesh)
        flat = dict(model.flat_leaves(specs))
        named = dict(model.flat_leaves(shapes))
        by_shape = {named[k]: flat[k] for k in named}
        p_sh = by_shape.__getitem__
        rep = NamedSharding(mesh, P())
        from jax.sharding import Mesh
        import numpy as np
        ref_mesh = Mesh(np.asarray(devices), ("x",))
        ref_sh = lambda s: NamedSharding(  # noqa: E731
            ref_mesh, P(*([None] * (len(s) - 1)), "x"))
    else:
        one = SingleDeviceSharding(devices[0])
        p_sh = ref_sh = lambda s: one  # noqa: E731
        rep = one
    params = _shapes(shapes, p_sh, jnp.bfloat16)
    opt_state = optax.tree_map_params(
        model.program_optimizer(conf),
        lambda s, p: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                          sharding=p.sharding),
        jax.eval_shape(init_opt, params), params)
    tokens = jax.ShapeDtypeStruct((w["batch"], w["seq"] + 1), jnp.int32,
                                  sharding=rep)
    out = {"topology": "v5e:2x2 (described)", "devices": chips}
    compiled = jax.jit(train_step, donate_argnums=(0, 1)).lower(
        params, opt_state, tokens).compile()
    out["program_step"] = _analysis(compiled)
    rp = _shapes(shapes, ref_sh, reference.STORE)
    out["reference_step"] = _analysis(
        reference.step_fn(conf, "f32").lower(rp, rp, tokens).compile())
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
