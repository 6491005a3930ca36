"""The one generator of inputs. A traffic mix is a data file of parameters
under `benchmark/traffic/`; everything it makes comes from `--seed`.

The same seed gives the same inputs; every seed gives the same sizes and
the same amount of work.
"""

from __future__ import annotations

import numpy as np


def seed_words(seed: int, n: int = 2) -> np.ndarray:
    """Any whole number (more than 32 signed bits hold) -> n uint32 words."""
    return np.random.SeedSequence(int(seed)).generate_state(n)


def jax_key(seed: int, stream: int = 0):
    import jax

    w = seed_words(seed, 2)
    return jax.random.fold_in(jax.random.PRNGKey(int(w[0] >> 1)),
                              int(w[1] >> 1) ^ stream)


def textured_frames(key, n: int, h: int, w: int, feature_px: int = 8,
                    max_shift: int = 4):
    """n pairs of float32 BGR frames in [0, 255], made on the device in one
    call: smooth random texture, the second frame the first translated by a
    whole number of pixels drawn per pair (so a flow exists that explains
    it). Returns (source, target) device arrays of shape (n, h, w, 3)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def make(k):
        k1, k2 = jax.random.split(k)
        base = jax.random.uniform(
            k1, (n, h // feature_px + 3, w // feature_px + 3, 3)) * 255.0
        canvas = jax.image.resize(base, (n, h + 16, w + 16, 3), "cubic")
        canvas = jnp.clip(canvas, 0.0, 255.0)
        uv = jax.random.randint(k2, (n, 2), -max_shift, max_shift + 1)
        src = canvas[:, 8:8 + h, 8:8 + w]
        tgt = jax.vmap(lambda c, s: jax.lax.dynamic_slice(
            c, (8 + s[1], 8 + s[0], 0), (h, w, 3)))(canvas, uv)
        return src, tgt

    return make(key)
