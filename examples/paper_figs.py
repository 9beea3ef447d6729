"""Reproductions of the paper's accuracy experiments (Figs. 3, 4, 7).

Each function returns (rows, summary); a row is (name, derived), with
the figure's numbers in derived. The runs are float64, as the paper's
MATLAB runs were: they invert ill-conditioned Gram matrices.

Run:  PYTHONPATH=src python examples/paper_figs.py [--fig 3,4,7] [--fast]
(Fig. 4 takes about 15 s; setting (a) diverges on purpose, its gamma
is above 1/d_max; (b) and (c) end within 0.01 of the centralized beta.)
"""

from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import consensus, dc_elm, elm
from repro.data.partition import partition_equal
from repro.data.sinc import make_sinc_dataset
from repro.data.synthetic_mnist import make_mnist36_dataset


# ---------------------------------------------------------------------------
# Fig. 3: centralized ELM MSE/DEV vs number of hidden neurons L
# ---------------------------------------------------------------------------


def fig3_centralized_sinc(trials: int = 10):
    rows = []
    Ls = [5, 10, 20, 50, 100, 200]
    C = 2**8
    for L in Ls:
        mses = []
        for t in range(trials):
            X, Y, Xt, Yt = make_sinc_dataset(
                jax.random.key(100 + t), num_nodes=1, per_node=5000,
                num_test=2000,
            )
            model = elm.train_centralized(
                jax.random.key(t), X[0], Y[0], num_features=L, C=C
            )
            mses.append(float(elm.mse(model, Xt, Yt)))
        mse, dev = float(np.mean(mses)), float(np.std(mses))
        rows.append((f"fig3/sinc_centralized_L{L}",
                     f"mse={mse:.5f};dev={dev:.5f}"))
    return rows, {"Ls": Ls}


# ---------------------------------------------------------------------------
# Fig. 4: DC-ELM on SinC — convergence and the documented divergence
# ---------------------------------------------------------------------------


def fig4_dcelm_sinc(iters: int = 300):
    from repro.core.features import make_random_features

    rows = []
    graph = consensus.paper_fig2()  # V=4, d_max=2
    X, Y, Xt, Yt = make_sinc_dataset(jax.random.key(0))
    X, Y = X.astype(jnp.float64), Y.astype(jnp.float64)
    settings = [
        ("a", 2**2, 1 / 1.9),  # gamma > 1/d_max: paper shows divergence
        ("b", 2**2, 1 / 2.1),
        ("c", 2**8, 1 / 2.1),
    ]
    fmap = make_random_features(
        jax.random.key(1), 1, 100, "sigmoid", dtype=X.dtype
    )
    for tag, C, gamma in settings:
        H = jax.vmap(fmap)(X)
        _, P_, Q_ = dc_elm.simulate_init(H, Y, C)
        state = dc_elm.simulate_init_from_stats(P_, Q_, C)
        trace_fn = dc_elm.average_empirical_risk_fn(fmap, Xt, Yt)
        # setting (a) deliberately exceeds the Thm. 2 bound (the
        # paper's divergence panel), so opt out of the gamma check
        final, risks = dc_elm.simulate_run(
            state, graph, gamma, C, iters, trace_fn=trace_fn,
            check_gamma=False,
        )
        beta_c = dc_elm.centralized_from_node_stats(P_, Q_, C)
        cent = elm.ELM(feature_map=fmap, beta=beta_c)
        r_c = float(elm.empirical_risk(cent(Xt), Yt))
        r_d0, r_dk = float(risks[0]), float(risks[-1])
        dist = float(dc_elm.distance_to(final.betas, beta_c))
        rows.append((
            f"fig4{tag}/C{C:g}_gamma{gamma:.3f}",
            f"Rc={r_c:.4f};Rd0={r_d0:.4f};Rdk={r_dk:.4f};dist={dist:.4f}",
        ))
    return rows, {}


# ---------------------------------------------------------------------------
# Fig. 7: MNIST(3v6 surrogate) over random geometric networks
# ---------------------------------------------------------------------------


def fig7_mnist(iters: int = 1500):
    rows = []
    X, T, Xt, Tt = make_mnist36_dataset(seed=0)
    X, T = jnp.asarray(X), jnp.asarray(T)
    Xt, Tt = jnp.asarray(Xt), jnp.asarray(Tt)
    L, C = 25, 2**-2
    # centralized reference (paper: 0.8989 for V=25 setup, 0.9200 for V=100)
    cent = elm.train_centralized(jax.random.key(0), X, T, num_features=L, C=C)
    acc_c = float(elm.accuracy(cent(Xt), Tt))
    rows.append(("fig7/centralized", f"acc={acc_c:.4f}"))
    for V, gamma, radius, seed in [(25, 0.076, 0.35, 1), (100, 0.038, 0.2, 2)]:
        g = consensus.random_geometric(V, radius, seed=seed)
        Xn, Tn = partition_equal(np.asarray(X), np.asarray(T), V)
        fmap = cent.feature_map
        H = jax.vmap(fmap)(jnp.asarray(Xn))
        state, P_, Q_ = dc_elm.simulate_init(H, jnp.asarray(Tn), C)
        trace_fn = dc_elm.test_error_fn(fmap, Xt, Tt)
        final, errs = dc_elm.simulate_run(
            state, g, gamma, C, iters, trace_fn=trace_fn
        )
        rows.append((
            f"fig7/V{V}",
            f"err0={float(errs[0]):.4f};errK={float(errs[-1]):.4f};"
            f"acc={1-float(errs[-1]):.4f};lambda2={g.algebraic_connectivity:.4f};"
            f"dmax={g.d_max:.0f};acc_centralized={acc_c:.4f}",
        ))
    return rows, {}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fig", default="3,4,7", help="comma-separated subset")
    ap.add_argument(
        "--fast", action="store_true", help="3 Fig. 3 trials, 300 Fig. 7 rounds"
    )
    args = ap.parse_args()
    jax.config.update("jax_enable_x64", True)
    figs = {
        "3": (fig3_centralized_sinc, {"trials": 3} if args.fast else {}),
        "4": (fig4_dcelm_sinc, {}),
        "7": (fig7_mnist, {"iters": 300} if args.fast else {}),
    }
    print("name,derived")
    for name in args.fig.split(","):
        fn, kw = figs[name]
        rows, _ = fn(**kw)
        for r in rows:
            print(",".join(r))


if __name__ == "__main__":
    main()
