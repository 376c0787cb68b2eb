"""mRMR as a first-class data-pipeline stage in front of model training.

    PYTHONPATH=src python examples/feature_selection_pipeline.py

The paper's motivating workflow: a wide dataset (more features than
observations) is reduced with distributed mRMR, then a downstream model is
trained on the selected columns.  We train the same logistic-regression
head (in JAX, plain gradient descent) on (a) all features, (b)
mRMR-selected, (c) randomly selected — showing mRMR keeps accuracy at a
fraction of the width.
"""

import jax
import jax.numpy as jnp
import numpy as np

from repro import MRMRSelector, PearsonMIScore
from repro.data.synthetic import continuous_wide_dataset

N_OBS, N_FEAT, K = 2_000, 8_192, 16


def train_head(Xtr, ytr, Xte, yte, steps=300, lr=0.5):
    """Logistic regression by full-batch gradient descent -> test accuracy."""
    w = (jnp.zeros(Xtr.shape[1]), jnp.zeros(()))

    def loss_fn(w, X, y):
        z = X @ w[0] + w[1]
        return jnp.mean(jnp.logaddexp(0.0, z) - y * z)

    @jax.jit
    def step(w, X, y):
        g = jax.grad(loss_fn)(w, X, y)
        return jax.tree.map(lambda p, d: p - lr * d, w, g)

    for _ in range(steps):
        w = step(w, Xtr, ytr)
    acc = jnp.mean(((Xte @ w[0] + w[1]) > 0) == (yte > 0.5))
    return float(acc)


def main():
    X, y = continuous_wide_dataset(N_OBS, N_FEAT, seed=1)
    X, y = np.asarray(X), np.asarray(y, np.float32)
    ntr = int(0.8 * N_OBS)
    Xtr, Xte, ytr, yte = X[:ntr], X[ntr:], y[:ntr], y[ntr:]

    # feature selection sees only the training split (no leakage);
    # Pearson score -> the planner picks the feature-sharded encoding.
    fs = MRMRSelector(num_select=K, score=PearsonMIScore()).fit(Xtr, ytr)
    print(f"planned encoding: {fs.plan_.encoding}")
    sel = np.asarray(fs.selected_)
    rng = np.random.default_rng(0)
    rand = rng.choice(N_FEAT, size=K, replace=False)

    acc_all = train_head(jnp.asarray(Xtr), jnp.asarray(ytr),
                         jnp.asarray(Xte), jnp.asarray(yte))
    acc_sel = train_head(jnp.asarray(Xtr[:, sel]), jnp.asarray(ytr),
                         jnp.asarray(Xte[:, sel]), jnp.asarray(yte))
    acc_rnd = train_head(jnp.asarray(Xtr[:, rand]), jnp.asarray(ytr),
                         jnp.asarray(Xte[:, rand]), jnp.asarray(yte))

    print(f"selected (mRMR/Pearson): {sorted(sel.tolist())}")
    print(f"test acc — all {N_FEAT} features: {acc_all:.3f}")
    print(f"test acc — {K} mRMR features:     {acc_sel:.3f}")
    print(f"test acc — {K} random features:   {acc_rnd:.3f}")
    assert acc_sel > acc_rnd + 0.05, "mRMR should beat random selection"
    return dict(selected=sel, acc_all=acc_all, acc_sel=acc_sel,
                acc_rnd=acc_rnd)


if __name__ == "__main__":
    main()
