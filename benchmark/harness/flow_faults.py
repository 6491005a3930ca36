"""Faults a flow train cell must be able to see, planted under the runner's
tap (`step_fault(tap)`): by the CPU tests at a toy size and by
`tools/calibrate.py` on the chip at the cell's own size. Never used by a
benchmark run.

The correlation's two are planted in its BACKWARD alone (the forward's
values stay what they were): `with_faulty_backward` wraps any correlation
`f(f1, f2, *static)`, the program's or the plain reference's, so the same
fault can be read in the program and in the reference put in its place.
"""

from __future__ import annotations


def unchanged_state(tap) -> None:
    """The step returns its state as it got it."""
    import jax
    import jax.numpy as jnp

    inner = tap.inner
    copy = lambda state: jax.tree_util.tree_map(jnp.copy, state)  # noqa: E731
    tap.inner = lambda state, batch: (state, inner(copy(state), batch)[1])


def half_batch(tap) -> None:
    """Half of the batch left out: the first half stands in for the second."""
    import jax.numpy as jnp

    inner = tap.inner

    def step(state, batch):
        n = batch["source"].shape[0] // 2
        return inner(state, {k: jnp.concatenate([v[:n], v[:n]])
                             for k, v in batch.items()})

    tap.inner = step


def with_faulty_backward(sound, mode: str):
    """`sound(f1, f2, *static)` with its forward as it is and its backward
    at fault. `zero`: no gradient reaches either feature map. `no_df2`:
    none reaches the second. `flipped`: the second map's gradient is
    gathered at the mirrored displacement (the cotangent's 441 maps read in
    reverse order; the grid is symmetric, so map i stands where map
    n*n-1-i should). The last is for the calibration's record only: at the
    seeded weights the features are all but constant over the image, the
    gradient through the cost volume does not depend on WHICH displacement
    a cotangent is booked to but at the image's border, and at the cell's
    size the whole first gradient moves by a hundred-thousandth of its norm
    (PERF.md section 6, PR 39): nothing a training step yields can see it."""
    import jax
    import jax.numpy as jnp

    def faulty(f1, f2, *static):
        @jax.custom_vjp
        def corr(a, b):
            return sound(a, b, *static)

        def fwd(a, b):
            return jax.vjp(lambda x, y: sound(x, y, *static), a, b)

        def bwd(vjp, g):
            df1, df2 = vjp(g)
            if mode == "zero":
                return jnp.zeros_like(df1), jnp.zeros_like(df2)
            if mode == "no_df2":
                return df1, jnp.zeros_like(df2)
            if mode == "flipped":
                return df1, vjp(g[..., ::-1])[1]
            raise ValueError(mode)

        corr.defvjp(fwd, bwd)
        return corr(f1, f2)

    return faulty


def corr_backward(mode: str):
    """The program's own step with the fault in its correlation's backward:
    the model's `correlation` is exchanged while the step is traced (its
    first call) and put back."""
    def fault(tap) -> None:
        from deepof_tpu.models import flownet_c as model

        inner = tap.inner

        def step(state, batch):
            sound = model.correlation
            model.correlation = with_faulty_backward(sound, mode)
            try:
                return inner(state, batch)
            finally:
                model.correlation = sound

        tap.inner = step
    return fault


FAULTS = {"unchanged_state": unchanged_state, "half_batch": half_batch,
          "corr_bwd_zero": corr_backward("zero"),
          "corr_bwd_no_df2": corr_backward("no_df2")}
#: the cells a fault can be planted in (the others' steps hold no correlation)
ONLY_IN = {"corr_bwd_zero": ("flownet_c_chairs",),
           "corr_bwd_no_df2": ("flownet_c_chairs",)}
