"""The plain objective that the seam test's L-BFGS run is held to: ℓ2-
regularised logistic regression over binary features, in float64
``jax.numpy``. Imports nothing of the program. Not a test file.

    objective = sum over rows of log(1 + exp(-y pred))
                + l2 / 2 * sum over features of w^2,
    pred = sum of the row's features' w, clipped to [-20, 20],

with y in {-1, +1}; a feature the model has no weight for adds 0.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

PRED_CLAMP = 20.0


def objective(rev, y, feaids, w, l2: float) -> float:
    """``rev`` uint64[rows, fields]: each row's features; ``feaids``
    uint64[n] ascending, the features the model holds, with ``w`` f32[n]
    their weights; ``y`` 0/1 labels."""
    rev = np.asarray(rev, np.uint64)
    feaids = np.asarray(feaids, np.uint64)
    pos = np.minimum(np.searchsorted(feaids, rev), len(feaids) - 1)
    held = feaids[pos] == rev
    with jax.enable_x64(True):
        wf = jnp.asarray(w, jnp.float64)
        x = jnp.where(jnp.asarray(held), wf[jnp.asarray(pos)], 0.0)
        pred = jnp.clip(x.sum(1), -PRED_CLAMP, PRED_CLAMP)
        yy = jnp.where(jnp.asarray(y) > 0, 1.0, -1.0)
        loss = jnp.sum(jnp.log1p(jnp.exp(-yy * pred)))
        return float(loss + 0.5 * l2 * jnp.sum(wf * wf))
