"""The system under test's training step, driven as the job runs it:
the program's `make_train_step` on the benchmark's weights and token
feed, one compiled object from the first step to the last.

`Trainer.first_steps` drives the first three steps through the same
compiled call and feed that the window then uses, and reads what the
correctness check compares: each step's loss, the first gradient as the
optimizer got it (from its state after one step), and each leaf's change
after three steps.
"""

from __future__ import annotations

import jax

from perfbench import model
from perfbench.reference import change_norms, leaf_norms

POOL = 8          # distinct token batches the feed cycles through


class Trainer:
    def __init__(self, conf: dict, seed: int, devices: list) -> None:
        from deepflow_tpu.models.llama import make_train_step, param_specs
        model.check_departures(conf)
        self.conf, self.seed = conf, seed
        self.w = model.widths(conf)
        cfg = model.program_config(conf)
        train_step, init_opt = make_train_step(
            cfg, optimizer=model.program_optimizer(conf))
        if len(devices) > 1:
            from jax.sharding import NamedSharding, PartitionSpec as P

            from deepflow_tpu.parallel.mesh import (
                make_mesh, named_sharding_tree)
            mesh = make_mesh(devices)
            p_sh = named_sharding_tree(param_specs(cfg), mesh)
            tok_sh = NamedSharding(mesh, P())
        else:
            from jax.sharding import SingleDeviceSharding
            p_sh = tok_sh = SingleDeviceSharding(devices[0])
        self.p_sh = p_sh
        self.params = model.make_weights(self.w, seed, p_sh)
        self.opt_state = jax.jit(init_opt, out_shardings=state_shardings(
            init_opt, self.params, p_sh, tok_sh))(self.params)
        self.pool = model.token_pool(self.w, seed, POOL, tok_sh)
        self.step_fn = jax.jit(train_step, donate_argnums=(0, 1)).lower(
            self.params, self.opt_state, self.pool[0]).compile()
        self.steps = 0

    def step(self):
        """One step on the next batch of the feed; returns the loss."""
        self.params, self.opt_state, loss = self.step_fn(
            self.params, self.opt_state, self.pool[self.steps % POOL])
        self.steps += 1
        return loss

    def first_steps(self) -> dict:
        """Steps 1-3 through the window's own call, and their readings."""
        assert self.steps == 0
        losses, grad = [], None
        for t in (1, 2, 3):
            losses.append(self.step())
            if t == 1:
                grad = jax.jit(lambda s: leaf_norms(
                    model.first_grad_from_state(self.conf, s)))(
                        self.opt_state)
        # the initial weights again, from the executable that made them
        p0 = model.make_weights(self.w, self.seed, self.p_sh)
        upd, moved = change_norms(self.params, p0)
        del p0
        return {"loss": [float(x) for x in losses],
                "grad": {k: float(v) for k, v in grad.items()},
                "update": {k: float(v) for k, v in upd.items()},
                "moved_share": {k: float(v) for k, v in moved.items()}}

    def free(self) -> None:
        """Drop the training state, so the reference has the chip."""
        for name in ("params", "opt_state", "pool", "step_fn"):
            setattr(self, name, None)


def state_shardings(init_opt, params, p_sh, rest):
    """Where the optimizer's state lives: each subtree shaped like the
    parameters (a moment) is split as they are, the rest (step counts)
    takes `rest`. Left to jit, the state's zeros, which depend on no
    input, all land whole on the first device."""
    pdef = jax.tree.structure(params)

    def like_params(x) -> bool:
        return jax.tree.structure(x) == pdef

    return jax.tree.map(lambda x: p_sh if like_params(x) else rest,
                        jax.eval_shape(init_opt, params), is_leaf=like_params)

