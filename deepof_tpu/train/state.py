"""Train state: one pytree carrying everything a step needs.

The reference checkpoints only model variables — optimizer state and the
epoch counter are lost on resume and the LR schedule restarts
(`flyingChairsTrain.py:156-161`, SURVEY.md §5.4). Here params, optimizer
state, step counter, and the PRNG key are one pytree, checkpointed whole.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
import optax
from flax import struct

from ..core.config import OptimConfig
from ..models.common import count_params


@struct.dataclass
class TrainState:
    step: jnp.ndarray
    params: Any
    opt_state: Any
    rng: jax.Array
    tx: optax.GradientTransformation = struct.field(pytree_node=False)

    def apply_gradients(self, grads) -> "TrainState":
        updates, new_opt = self.tx.update(grads, self.opt_state, self.params)
        return self.replace(
            step=self.step + 1,
            params=optax.apply_updates(self.params, updates),
            opt_state=new_opt,
        )


def make_optimizer(cfg: OptimConfig, schedule: Callable) -> optax.GradientTransformation:
    """Adam with the reference's hyper-parameters (`flyingChairsTrain.py:124`)
    plus optional global-norm gradient clipping and gradient accumulation
    (new capabilities)."""
    accum = max(cfg.grad_accum, 1)
    if accum > 1:
        # MultiSteps' inner count advances once per optimizer update (every
        # `accum` micro-steps); stretch the schedule so LR-decay boundaries
        # stay at the same number of *data* batches as without accumulation.
        inner_schedule = lambda count: schedule(count * accum)  # noqa: E731
    else:
        inner_schedule = schedule
    tx = optax.adam(inner_schedule, b1=cfg.beta1, b2=cfg.beta2,
                    eps=cfg.adam_eps)
    if cfg.grad_clip_norm:
        tx = optax.chain(optax.clip_by_global_norm(cfg.grad_clip_norm), tx)
    if accum > 1:
        tx = optax.MultiSteps(tx, every_k_schedule=accum)
    return tx


def create_train_state(
    model,
    example_input: jnp.ndarray,
    tx: optax.GradientTransformation,
    seed: int = 0,
    log: Callable[[str], None] | None = None,
) -> TrainState:
    """Initialize params (bilinear deconv init is built into the modules via
    `bilinear_kernel_init`) and the optimizer.

    Prints the parameter count — the reference's architecture checksum
    (`flyingChairsTrain.py:106-118`).
    """
    rng, init_rng = jax.random.split(jax.random.PRNGKey(seed))

    # ONE jitted executable for params + optimizer state. Eagerly,
    # `model.init` runs the whole forward op by op at the example's full
    # resolution and `tx.init` adds a dispatch per leaf: hundreds of tiny
    # compiles on an accelerator. Under jit the forward is dead code (only
    # the initializers feed the outputs) and XLA drops it.
    def init(key, x):
        params = model.init({"params": key}, x)["params"]
        return params, tx.init(params)

    params, opt_state = jax.jit(init)(init_rng, example_input)
    if log:
        log(f"model parameters: {count_params(params):,}")
    return TrainState(
        step=jnp.zeros((), jnp.int32),
        params=params,
        opt_state=opt_state,
        rng=rng,
        tx=tx,
    )
