"""The smoke's jitted programs compiled for a described TPU v5e (no chip).

The TPU compiler is installed here and compiles for a 2x2 v5e topology
that is described, not attached: it refuses what would not fit or not
partition, at no chip time. The topology is described inside a fixture
so that only the worker that runs these tests loads libtpu.
"""

import dataclasses
import os

import pytest

from examples.smoke_checks import hlo_collective_ops

HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip cannot be read back without one
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


def _smoke_step(n_layers: int):
    """The chip smoke's train step: llama7b() widths, SGD momentum."""
    import optax

    from deepflow_tpu.models.llama import LlamaConfig, make_train_step
    cfg = dataclasses.replace(LlamaConfig.llama7b(), n_layers=n_layers)
    opt = optax.sgd(3e-4, momentum=0.9)
    step, init_opt = make_train_step(cfg, optimizer=opt)
    return cfg, opt, step, init_opt


def _shapes(cfg, init_opt, params_sharding, opt, tokens_sharding,
            batch=2, seq=1024):
    """Params, momentum and tokens as ShapeDtypeStructs (no arrays)."""
    import jax
    import jax.numpy as jnp
    import optax

    from deepflow_tpu.models.llama import init_params
    pshape = jax.eval_shape(lambda: init_params(cfg, jax.random.key(0)))
    params = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        pshape, params_sharding(pshape))
    # momentum mirrors the params, sharding included
    opt_state = optax.tree_map_params(
        opt, lambda s, p: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                               sharding=p.sharding),
        jax.eval_shape(init_opt, pshape), params)
    tokens = jax.ShapeDtypeStruct((batch, seq + 1), jnp.int32,
                                  sharding=tokens_sharding)
    return params, opt_state, tokens


def _compile_one_chip(topo, n_layers):
    import jax
    from jax.sharding import SingleDeviceSharding
    one = SingleDeviceSharding(topo.devices[0])
    cfg, opt, step, init_opt = _smoke_step(n_layers)
    args = _shapes(cfg, init_opt,
                   lambda t: jax.tree.map(lambda _: one, t), opt, one)
    return jax.jit(step, donate_argnums=(0, 1)).lower(*args).compile()


def test_smoke_step_fits_one_v5e(topo):
    ma = _compile_one_chip(topo, 4).memory_analysis()
    used = ma.argument_size_in_bytes + ma.temp_size_in_bytes
    assert ma.argument_size_in_bytes > 3 * 10**9  # params + momentum
    assert used < HBM_BYTES


def test_sharded_step_compiles_with_collectives(topo):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from deepflow_tpu.models.llama import param_specs
    from deepflow_tpu.parallel.mesh import make_mesh, named_sharding_tree
    mesh = make_mesh(list(topo.devices))
    assert dict(mesh.shape) == {"data": 1, "fsdp": 1, "tensor": 4}
    cfg, opt, step, init_opt = _smoke_step(4)
    specs = named_sharding_tree(param_specs(cfg), mesh)
    args = _shapes(cfg, init_opt, lambda _: specs, opt,
                   NamedSharding(mesh, P()))
    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(*args).compile()
    text = compiled.as_text()
    ops = hlo_collective_ops(text)
    # tensor parallelism all-reduces activations; the smoke counts these
    assert ops and all("all-reduce" in op for op in ops)
    assert "replica_groups=[1,4]" in text
    per_device = compiled.memory_analysis()
    assert per_device.argument_size_in_bytes < 10**9  # a quarter each


def test_eight_layers_do_not_fit_one_v5e(topo):
    """Pins the depth cut: 8 layers at batch 2 x seq 1024 is refused."""
    try:
        ma = _compile_one_chip(topo, 8).memory_analysis()
    except Exception as e:
        assert "RESOURCE_EXHAUSTED" in str(e) or "memory" in str(e).lower()
        return
    assert ma.argument_size_in_bytes + ma.temp_size_in_bytes > HBM_BYTES
