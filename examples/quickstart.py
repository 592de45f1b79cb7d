"""Quickstart: full XMR pipeline in ~a minute on CPU.

Builds a synthetic product-search-like dataset, clusters labels (PIFA +
balanced bisection), trains the per-level rankers, sparsifies, and serves
with every MSCM variant — verifying the paper's exactness claim and showing
the speedup live.

    PYTHONPATH=src python examples/quickstart.py
"""

import time

import jax.numpy as jnp
import numpy as np

from repro.data import synthetic_labeled_dataset
from repro.metrics import precision_at_k
from repro.trees.train import train_xmr_model


def main() -> None:
    rng = np.random.default_rng(0)
    print("1) generating synthetic dataset (512 labels, d=1024) ...")
    ds = synthetic_labeled_dataset(
        rng, n_labels=512, d=1024, n_train=2048, n_test=512, query_nnz=20
    )

    print("2) clustering + training per-level rankers (branching 8) ...")
    t0 = time.time()
    model = train_xmr_model(
        ds.x_train, ds.y_train, ds.n_labels, branching=8, rng=rng,
        nnz_per_col=64, steps=150,
    )
    print(f"   trained in {time.time() - t0:.1f}s; "
          f"model memory {model.tree.memory_bytes() / 1e6:.1f} MB")

    xi, xv = ds.x_test.to_ell(64)
    xi, xv = jnp.asarray(xi), jnp.asarray(xv)

    print("3) serving with each masked-matmul method:")
    ref_labels = None
    for method in ("vanilla", "mscm_dense", "mscm_searchsorted",
                   "mscm_pallas_grouped"):
        scores, labels = model.predict(xi, xv, beam=16, topk=5, method=method)
        t0 = time.time()
        for _ in range(3):
            model.predict(xi, xv, beam=16, topk=5, method=method)
        dt = (time.time() - t0) / 3 / len(ds.y_test)
        p1 = precision_at_k(labels, ds.y_test, 1)
        if ref_labels is None:
            ref_labels = labels
        exact = "exact-match" if (labels == ref_labels).all() else "MISMATCH!"
        print(f"   {method:20s} P@1={p1:.3f}  {1e6 * dt:7.1f} us/query  [{exact}]")

    print("\nAll methods return identical rankings (paper's 'free of charge'"
          " property); MSCM variants are the fast ones.")


if __name__ == "__main__":
    main()
