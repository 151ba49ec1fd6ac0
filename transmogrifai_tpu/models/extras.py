"""Remaining model families: naive Bayes, MLP, generalized linear models,
isotonic calibration.

Parity: reference ``OpNaiveBayes`` (Spark multinomial NB),
``OpMultilayerPerceptronClassifier`` (Spark MLP),
``OpGeneralizedLinearRegression`` (Spark GLR families/links), and
``IsotonicRegressionCalibrator`` (Spark IsotonicRegression on scores).

All device-native: NB fits with one ``onehot(y)^T @ X`` matmul; the MLP is a
hand-rolled (no flax) Adam ``lax.scan``; GLR runs family NLL gradient
descent; isotonic uses host PAV (tiny data: one point per distinct score).
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import optax

from transmogrifai_tpu import frame as fr
from transmogrifai_tpu.models.base import PredictionModel, Predictor
from transmogrifai_tpu.stages.base import Estimator
from transmogrifai_tpu.types import feature_types as ft

__all__ = [
    "OpNaiveBayes", "NaiveBayesModel",
    "OpMultilayerPerceptronClassifier", "MLPModel",
    "OpGeneralizedLinearRegression", "GLMModel",
    "IsotonicRegressionCalibrator", "IsotonicCalibratorModel",
]


# ---------------------------------------------------------------------------
# Multinomial naive Bayes
# ---------------------------------------------------------------------------

class NaiveBayesModel(PredictionModel):
    def __init__(self, log_prior=None, log_theta=None,
                 uid: Optional[str] = None):
        # may be device arrays during the CV sweep (no host pull);
        # conversion happens lazily on serialization/introspection
        self.log_prior = log_prior if log_prior is not None else np.zeros(2)
        self.log_theta = log_theta if log_theta is not None \
            else np.zeros((0, 2))
        super().__init__(uid=uid)

    def device_params(self):
        return (jnp.asarray(self.log_prior, jnp.float32),
                jnp.asarray(self.log_theta, jnp.float32))

    def quantize_device_params(self, precision):
        if precision != "int8":
            return None
        from transmogrifai_tpu.utils.precision import quantize_weights
        log_prior, log_theta = self.device_params()
        return (log_prior, quantize_weights(log_theta))

    def device_apply(self, params, col: fr.VectorColumn) -> fr.PredictionColumn:
        log_prior, log_theta = params
        X = jnp.maximum(col.values, 0.0)  # multinomial NB needs counts
        logits = X @ log_theta + log_prior
        prob = jax.nn.softmax(logits, axis=-1)
        pred = jnp.argmax(logits, axis=-1).astype(jnp.float32)
        return fr.PredictionColumn(pred, logits, prob)

    def fitted_state(self):
        return {"log_prior": np.asarray(self.log_prior, np.float64),
                "log_theta": np.asarray(self.log_theta, np.float64)}

    def set_fitted_state(self, state):
        self.log_prior = np.asarray(state["log_prior"], np.float64)
        self.log_theta = np.asarray(state["log_theta"], np.float64)

    def config(self):
        return {}

    @classmethod
    def from_config(cls, config, uid=None):
        return cls(uid=uid)

    def feature_contributions(self):
        lt = np.asarray(self.log_theta)
        return lt[:, -1] - lt[:, 0] if lt.shape[1] >= 2 else lt[:, 0]


@functools.partial(jax.jit, static_argnames=("n_classes",))
def _nb_fit(X, y, w, smoothing, *, n_classes: int):
    """One multinomial-NB closed-form fit (smoothing traced so the same
    program serves every grid point and vmaps over folds)."""
    Y = jax.nn.one_hot(y.astype(jnp.int32), n_classes) * w[:, None]
    Xp = jnp.maximum(X, 0.0)
    class_counts = jnp.sum(Y, axis=0)                      # [C]
    feat_counts = Xp.T @ Y                                 # [d, C]
    log_prior = jnp.log(class_counts / jnp.sum(class_counts))
    totals = jnp.sum(feat_counts, axis=0, keepdims=True)
    d = X.shape[1]
    log_theta = jnp.log((feat_counts + smoothing)
                        / (totals + smoothing * d))
    return log_prior, log_theta


class OpNaiveBayes(Predictor):
    """Multinomial NB with Laplace smoothing. Negative feature values are
    clipped to zero (Spark NB rejects them outright; clipping keeps the
    one-hot/hashed-count columns NB actually suits)."""

    default_params = {"smoothing": 1.0}

    def fit_arrays(self, X, y, w, params):
        smoothing = float(params.get("smoothing", 1.0))
        n_classes = max(int(np.asarray(jnp.max(y))) + 1, 2)
        log_prior, log_theta = _nb_fit(X, y, w, jnp.float32(smoothing),
                                       n_classes=n_classes)
        return NaiveBayesModel(log_prior=np.asarray(log_prior),
                               log_theta=np.asarray(log_theta))

    def grid_predict_scores(self, models, X):
        """[G, n] binary log-odds margins for the per-fold loop's batched
        metric (None past two classes: the loop then scores model by
        model; the fold-stacked path scores every class,
        ``grid_predict_scores_folds``)."""
        if not models:
            return None
        lt = jnp.stack([jnp.asarray(m.log_theta, jnp.float32)
                        for m in models])
        lp = jnp.stack([jnp.asarray(m.log_prior, jnp.float32)
                        for m in models])
        if lt.shape[-1] != 2:
            return None
        logits = jnp.einsum("nd,gdc->gnc", jnp.maximum(X, 0.0), lt) \
            + lp[:, None, :]
        return logits[..., 1] - logits[..., 0]

    # -- fold-stacked sweep --------------------------------------------------
    def grid_fit_arrays_folds(self, X, y, w, grid, _n_classes=None):
        """Closed-form fit vmapped over (fold x smoothing grid) — one
        program for the whole family sweep; model params stay on device.
        ``_n_classes`` elides the class-count sync on the one-sync
        dispatch path (the selector's once-per-sweep hint). NB's refit
        stays the cold closed form — a one-matmul fit has nothing to warm
        start."""
        if not grid:
            return []
        n_classes = (int(_n_classes) if _n_classes is not None
                     else max(int(np.asarray(jnp.max(y))) + 1, 2))
        sm = jnp.asarray([float({**self.params, **g}.get("smoothing", 1.0))
                          for g in grid], jnp.float32)
        inner = lambda Xk, yk, wk: jax.vmap(  # noqa: E731
            lambda s: _nb_fit(Xk, yk, wk, s, n_classes=n_classes))(sm)
        lp, lt = jax.vmap(inner)(X, y, w)  # [k, G, C], [k, G, d, C]
        return [[NaiveBayesModel(log_prior=lp[f, j], log_theta=lt[f, j])
                 for j in range(len(grid))] for f in range(int(X.shape[0]))]

    def grid_predict_scores_folds(self, models, X):
        """``[k, G, n_va]`` binary log-odds margins, or ``[k, G, C, n_va]``
        class log-posteriors past two classes (the class axis before the
        rows, as ``models/linear.py::_fold_class_scores`` lays them)."""
        if not models or not models[0]:
            return None
        lt = jnp.stack([jnp.stack([jnp.asarray(m.log_theta, jnp.float32)
                                   for m in row]) for row in models])
        lp = jnp.stack([jnp.stack([jnp.asarray(m.log_prior, jnp.float32)
                                   for m in row]) for row in models])
        if lt.shape[-1] != 2:
            return jnp.einsum("knd,kgdc->kgcn", jnp.maximum(X, 0.0), lt) \
                + lp[:, :, :, None]
        logits = jnp.einsum("knd,kgdc->kgnc", jnp.maximum(X, 0.0), lt) \
            + lp[:, :, None, :]
        return logits[..., 1] - logits[..., 0]


# ---------------------------------------------------------------------------
# Multilayer perceptron
# ---------------------------------------------------------------------------

def _mlp_descent(X, y, w, params0, *, max_iter: int, step_size):
    """Adam descent from explicit layer parameters (shared by the cold
    ``_train_mlp`` and the warm-started winner refit)."""
    n = X.shape[0]
    wsum = jnp.maximum(jnp.sum(w), 1.0)

    def forward(params, x):
        h = x
        for (W, b) in params[:-1]:
            h = jnp.tanh(h @ W + b)
        W, b = params[-1]
        return h @ W + b

    def loss(params):
        logits = forward(params, X)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -logp[jnp.arange(n), y.astype(jnp.int32)]
        return jnp.sum(nll * w) / wsum

    opt = optax.adam(step_size)
    state0 = opt.init(params0)

    def step(carry, _):
        params, opt_state = carry
        l, grads = jax.value_and_grad(loss)(params)
        updates, opt_state = opt.update(grads, opt_state)
        return (optax.apply_updates(params, updates), opt_state), l

    (params, _), _ = jax.lax.scan(step, (params0, state0), None,
                                  length=max_iter)
    return params


@functools.partial(jax.jit, static_argnames=("layers", "max_iter", "seed"))
def _train_mlp(X, y, w, *, layers: tuple, max_iter: int, seed: int,
               step_size):
    d = X.shape[1]
    sizes = (d,) + layers
    key = jax.random.PRNGKey(seed)
    keys = jax.random.split(key, len(sizes) - 1)
    params0 = []
    for i, k in enumerate(keys):
        scale = jnp.sqrt(2.0 / sizes[i])
        params0.append((jax.random.normal(k, (sizes[i], sizes[i + 1]))
                        * scale, jnp.zeros(sizes[i + 1])))
    return _mlp_descent(X, y, w, params0, max_iter=max_iter,
                        step_size=step_size)


def _train_mlp_from(X, y, w, params0, *, max_iter: int, step_size):
    """Warm-started MLP refit (round 9): the same descent initialized
    from the fold-averaged winning-lane parameters instead of a fresh
    PRNG draw."""
    return _mlp_descent(X, y, w, params0, max_iter=max_iter,
                        step_size=step_size)


_MLP_WARM = None


def _mlp_warm_program():
    """Donated-buffer compiled warm MLP refit (argnum 3 = the init
    parameter pytree, consumed exactly once)."""
    global _MLP_WARM
    if _MLP_WARM is None:
        from transmogrifai_tpu.models.base import compile_refit
        _MLP_WARM = compile_refit(_train_mlp_from, donate_argnums=(3,),
                                  static_argnames=("max_iter",))
    return _MLP_WARM


class MLPModel(PredictionModel):
    def __init__(self, params=None, uid: Optional[str] = None):
        self.params = params or []  # list[(W, b)] as np arrays
        super().__init__(uid=uid)

    def device_params(self):
        return tuple((jnp.asarray(W, jnp.float32), jnp.asarray(b, jnp.float32))
                     for W, b in self.params)

    def quantize_device_params(self, precision):
        if precision != "int8":
            return None
        from transmogrifai_tpu.utils.precision import quantize_weights
        return tuple((quantize_weights(W), b) for W, b in self.device_params())

    def device_apply(self, params, col: fr.VectorColumn) -> fr.PredictionColumn:
        h = col.values
        for (W, b) in params[:-1]:
            h = jnp.tanh(h @ W + b)
        W, b = params[-1]
        logits = h @ W + b
        prob = jax.nn.softmax(logits, axis=-1)
        pred = jnp.argmax(logits, axis=-1).astype(jnp.float32)
        return fr.PredictionColumn(pred, logits, prob)

    def fitted_state(self):
        state = {"n_layers": np.asarray(len(self.params))}
        for i, (W, b) in enumerate(self.params):
            state[f"W{i}"] = np.asarray(W)
            state[f"b{i}"] = np.asarray(b)
        return state

    def set_fitted_state(self, state):
        n = int(state["n_layers"])
        self.params = [(np.asarray(state[f"W{i}"]), np.asarray(state[f"b{i}"]))
                       for i in range(n)]

    def config(self):
        return {}

    @classmethod
    def from_config(cls, config, uid=None):
        return cls(uid=uid)


class OpMultilayerPerceptronClassifier(Predictor):
    default_params = {"layers": (10, 10), "max_iter": 200,
                      "step_size": 0.01, "seed": 42}

    def fit_arrays(self, X, y, w, params):
        p = {**self.default_params, **params}
        n_classes = max(int(np.asarray(jnp.max(y))) + 1, 2)
        layers = tuple(int(x) for x in p["layers"]) + (n_classes,)
        trained = _train_mlp(X, y, w, layers=layers,
                             max_iter=int(p["max_iter"]),
                             seed=int(p["seed"]),
                             step_size=jnp.float32(p["step_size"]))
        return MLPModel(params=[(np.asarray(W), np.asarray(b))
                                for W, b in trained])

    def grid_predict_scores(self, models, X):
        """[G, n] binary margins when all grid models share layer shapes
        (None otherwise) — keeps both sweep paths on one metric program."""
        folds = self.grid_predict_scores_folds([models], X[None])
        return None if folds is None else folds[0]

    def fold_stack_unit_width(self, grid):
        """Hidden activations dominate the MLP's per-row residency: the
        widest layer (x2 for forward+grad) across the grid."""
        widths = [max(tuple({**self.default_params, **self.params, **g}
                            ["layers"]) or (1,)) for g in grid] or [1]
        return 2 * max(widths) + 4

    # -- fold-stacked sweep --------------------------------------------------
    def grid_fit_arrays_folds(self, X, y, w, grid, _n_classes=None):
        """Fold-stacked MLP sweep: step_size is the traced grid axis, one
        vmap-of-vmap Adam program per distinct (layers, max_iter, seed)
        combo; fitted params stay device views. ``_n_classes`` elides the
        class-count sync (the selector's once-per-sweep hint)."""
        if not grid:
            return []
        merged = [{**self.default_params, **self.params, **g} for g in grid]
        n_classes = (int(_n_classes) if _n_classes is not None
                     else max(int(np.asarray(jnp.max(y))) + 1, 2))
        k = int(X.shape[0])
        models: list[list] = [[None] * len(grid) for _ in range(k)]
        by_kw: dict[tuple, list[int]] = {}
        for i, p in enumerate(merged):
            layers = tuple(int(x) for x in p["layers"]) + (n_classes,)
            by_kw.setdefault((layers, int(p["max_iter"]), int(p["seed"])),
                             []).append(i)
        for (layers, mi, seed), idxs in by_kw.items():
            ss = jnp.asarray([float(merged[i]["step_size"]) for i in idxs],
                             jnp.float32)
            inner = lambda Xk, yk, wk, _l=layers, _m=mi, _s=seed: jax.vmap(  # noqa: E731,E501
                lambda s: _train_mlp(Xk, yk, wk, layers=_l, max_iter=_m,
                                     seed=_s, step_size=s))(ss)
            trained = jax.vmap(inner)(X, y, w)  # leaves [k, g, ...]
            for f in range(k):
                for j, i in enumerate(idxs):
                    models[f][i] = MLPModel(
                        params=[(W[f, j], b[f, j]) for W, b in trained])
        return models

    def grid_predict_scores_folds(self, models, X):
        """``[k, G, n_va]`` binary margins via one stacked forward pass
        (``[k, G, C, n_va]`` class logits past two classes); None when
        grid models have heterogeneous layer shapes."""
        if not models or not models[0]:
            return None
        shapes = {tuple((tuple(W.shape), tuple(b.shape)) for W, b in m.params)
                  for row in models for m in row}
        if len(shapes) != 1:
            return None
        rows = [jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                                       *[m.params for m in row])
                for row in models]
        stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *rows)

        def fwd(params, Xk):
            h = Xk
            for (W, b) in params[:-1]:
                h = jnp.tanh(h @ W + b)
            W, b = params[-1]
            return h @ W + b

        z = jax.vmap(lambda p_row, Xk: jax.vmap(
            lambda p: fwd(p, Xk))(p_row))(stacked, X)  # [k, G, n, C]
        if z.shape[-1] != 2:
            return jnp.moveaxis(z, -1, 2)
        return z[..., 1] - z[..., 0]

    def grid_scores_folds_retained(self, X, y, w, grid, Xva,
                                   _n_classes=None):
        """One-sync dispatch unit: stacked scores plus the ``[k][G]``
        fitted-model nest retained as the warm-refit handle (the layer
        parameters are device views of the stacked result). A subclass
        overriding ``grid_scores_folds`` keeps its semantics (delegate,
        no warm handle)."""
        if type(self).grid_scores_folds is not Predictor.grid_scores_folds:
            return super().grid_scores_folds_retained(
                X, y, w, grid, Xva, _n_classes=_n_classes)
        if not grid:
            return None, None
        import inspect
        kw = {}
        if _n_classes is not None and "_n_classes" in \
                inspect.signature(self.grid_fit_arrays_folds).parameters:
            kw["_n_classes"] = _n_classes
        models = self.grid_fit_arrays_folds(X, y, w, grid, **kw)
        if models is None:
            return None, None
        scores = self.grid_predict_scores_folds(models, Xva)
        if scores is None:
            return None, None
        return scores, models

    def supports_warm_refit(self) -> bool:
        return True

    def refit_winner(self, X, y, w, params, *, warm=None, lane=None,
                     hints=None):
        """Full-data refit warm-started from the fold-AVERAGED layer
        parameters of the winning lane (donated-buffer program). Falls
        back to the cold PRNG init when the refit's layer shapes differ
        from the sweep's (class count shifted between fold and full
        data)."""
        p = {**self.default_params, **self.params, **params}
        if warm is None or lane is None:
            return self.fit_arrays(X, y, w, p), False
        n_classes = max(int(np.asarray(jnp.max(y))) + 1, 2)
        sizes = (int(X.shape[1]),) + tuple(int(x) for x in p["layers"]) \
            + (n_classes,)
        expect = [(sizes[i], sizes[i + 1]) for i in range(len(sizes) - 1)]
        lane_params = [row[int(lane)].params for row in warm]
        if [tuple(np.shape(W)) for W, _ in lane_params[0]] != expect:
            return self.fit_arrays(X, y, w, p), False
        params0 = jax.tree_util.tree_map(
            lambda *xs: jnp.mean(jnp.stack(
                [jnp.asarray(x, jnp.float32) for x in xs]), axis=0),
            *lane_params)
        trained = _mlp_warm_program()(
            X, y, w, params0, max_iter=int(p["max_iter"]),
            step_size=jnp.float32(p["step_size"]))
        return MLPModel(params=[(np.asarray(W), np.asarray(b))
                                for W, b in trained]), True


# ---------------------------------------------------------------------------
# Generalized linear regression
# ---------------------------------------------------------------------------

_FAMILIES = ("gaussian", "binomial", "poisson", "gamma", "tweedie")


def _glm_descent(Xs, y, w, wsum, params0, *, family: str, max_iter: int,
                 fit_intercept: bool, reg_param, var_power):
    """Family-NLL Adam descent from an explicit fit-space init (shared by
    the cold ``_train_glm`` and the warm-started winner refit)."""

    def nll(params):
        beta, b0 = params
        eta = Xs @ beta + b0
        if family == "gaussian":
            m = eta
            ll = -0.5 * (y - m) ** 2
        elif family == "binomial":
            ll = y * eta - jnp.logaddexp(0.0, eta)
        elif family == "poisson":
            ll = y * eta - jnp.exp(eta)
        elif family == "tweedie":
            # compound-Poisson quasi-likelihood, log link, 1 < p < 2
            # (Spark GLR tweedie): ll = y*mu^(1-p)/(1-p) - mu^(2-p)/(2-p).
            # Computed as exp(k*eta) directly: materializing mu = exp(eta)
            # first overflows float32 at |eta| ~ 88 and poisons the scan
            # with inf/nan long before these forms do
            ll = (y * jnp.exp((1.0 - var_power) * eta) / (1.0 - var_power)
                  - jnp.exp((2.0 - var_power) * eta) / (2.0 - var_power))
        else:  # gamma with log link (shape fixed)
            ll = -y * jnp.exp(-eta) - eta
        return -jnp.sum(ll * w) / wsum + reg_param * 0.5 * jnp.sum(beta ** 2)

    opt = optax.adam(0.1)
    state0 = opt.init(params0)

    def step(carry, _):
        params, opt_state = carry
        l, grads = jax.value_and_grad(nll)(params)
        if not fit_intercept:
            grads = (grads[0], jnp.zeros_like(grads[1]))
        updates, opt_state = opt.update(grads, opt_state)
        return (optax.apply_updates(params, updates), opt_state), l

    (params, _), _ = jax.lax.scan(step, (params0, state0), None,
                                  length=max_iter)
    return params


def _glm_fit_space(X, w):
    wsum = jnp.maximum(jnp.sum(w), 1.0)
    mu = jnp.sum(X * w[:, None], axis=0) / wsum
    sd = jnp.sqrt(jnp.maximum(
        jnp.sum(((X - mu) ** 2) * w[:, None], axis=0) / wsum, 1e-12))
    return (X - mu) / sd, mu, sd, wsum


@functools.partial(jax.jit, static_argnames=("family", "max_iter",
                                             "fit_intercept"))
def _train_glm(X, y, w, *, family: str, max_iter: int, fit_intercept: bool,
               reg_param, var_power=jnp.float32(1.5)):
    d = X.shape[1]
    Xs, mu, sd, wsum = _glm_fit_space(X, w)
    params0 = (jnp.zeros(d, jnp.float32), jnp.float32(0.0))
    beta, b0 = _glm_descent(Xs, y, w, wsum, params0, family=family,
                            max_iter=max_iter, fit_intercept=fit_intercept,
                            reg_param=reg_param, var_power=var_power)
    beta_orig = beta / sd
    b_orig = b0 - jnp.sum(beta * mu / sd)
    return beta_orig, b_orig


def _train_glm_from(X, y, w, beta_init, b_init, *, family: str,
                    max_iter: int, fit_intercept: bool, reg_param,
                    var_power):
    """Warm-started GLM refit (round 9): init given in ORIGINAL feature
    space (the fold-back space the stacked sweep parameters live in),
    mapped into the refit data's own standardized space."""
    Xs, mu, sd, wsum = _glm_fit_space(X, w)
    params0 = (beta_init * sd, b_init + mu @ beta_init)
    beta, b0 = _glm_descent(Xs, y, w, wsum, params0, family=family,
                            max_iter=max_iter, fit_intercept=fit_intercept,
                            reg_param=reg_param, var_power=var_power)
    beta_orig = beta / sd
    b_orig = b0 - jnp.sum(beta * mu / sd)
    return beta_orig, b_orig


_GLM_WARM = None


def _glm_warm_program():
    """Donated-buffer compiled warm GLM refit (argnums 3/4 = the init
    arrays, consumed exactly once)."""
    global _GLM_WARM
    if _GLM_WARM is None:
        from transmogrifai_tpu.models.base import compile_refit
        _GLM_WARM = compile_refit(
            _train_glm_from, donate_argnums=(3, 4),
            static_argnames=("family", "max_iter", "fit_intercept"))
    return _GLM_WARM


class GLMModel(PredictionModel):
    def __init__(self, weights=None, intercept=0.0,
                 family: str = "gaussian", uid: Optional[str] = None):
        # may be device arrays during the CV sweep (no host pull);
        # conversion happens lazily on serialization/introspection
        self.weights = weights if weights is not None else np.zeros(0)
        self.intercept = intercept
        self.family = family
        super().__init__(uid=uid)

    def device_params(self):
        return (jnp.asarray(self.weights, jnp.float32),
                jnp.asarray(self.intercept, jnp.float32))

    def quantize_device_params(self, precision):
        if precision != "int8":
            return None
        from transmogrifai_tpu.utils.precision import quantize_weights
        W, b = self.device_params()
        return (quantize_weights(W), b)

    def device_apply(self, params, col: fr.VectorColumn) -> fr.PredictionColumn:
        W, b = params
        eta = col.values @ W + b
        if self.family == "gaussian":
            mean = eta
        elif self.family == "binomial":
            mean = jax.nn.sigmoid(eta)
        else:
            mean = jnp.exp(eta)
        n = mean.shape[0]
        empty = jnp.zeros((n, 0), jnp.float32)
        return fr.PredictionColumn(mean, empty, empty)

    def fitted_state(self):
        return {"weights": np.asarray(self.weights, np.float64),
                "intercept": np.float64(self.intercept)}

    def set_fitted_state(self, state):
        self.weights = np.asarray(state["weights"], np.float64)
        self.intercept = float(state["intercept"])

    def config(self):
        return {"family": self.family}

    @classmethod
    def from_config(cls, config, uid=None):
        return cls(family=config.get("family", "gaussian"), uid=uid)

    def feature_contributions(self):
        return np.asarray(self.weights)


class OpGeneralizedLinearRegression(Predictor):
    default_params = {"family": "gaussian", "reg_param": 0.0,
                      "max_iter": 300, "fit_intercept": True,
                      "variance_power": 1.5}

    def fit_arrays(self, X, y, w, params):
        p = {**self.default_params, **params}
        family = p["family"]
        if family not in _FAMILIES:
            raise ValueError(f"Unknown GLM family {family!r}")
        vp = float(p["variance_power"])
        if family == "tweedie" and not 1.0 < vp < 2.0:
            raise ValueError(
                f"tweedie variance_power must be in (1, 2), got {vp}")
        beta, b0 = _train_glm(X, y, w, family=family,
                              max_iter=int(p["max_iter"]),
                              fit_intercept=bool(p["fit_intercept"]),
                              reg_param=jnp.float32(p["reg_param"]),
                              var_power=jnp.float32(vp))
        return GLMModel(weights=np.asarray(beta), intercept=float(b0),
                        family=family)

    def grid_predict_scores(self, models, X):
        """[G, n] mean predictions through the family link (None when grid
        points mix families) — keeps both sweep paths on one metric
        program."""
        folds = self.grid_predict_scores_folds([models], X[None])
        return None if folds is None else folds[0]

    # -- fold-stacked sweep --------------------------------------------------
    def grid_fit_arrays_folds(self, X, y, w, grid):
        """Fold-stacked GLM sweep: reg_param/variance_power are the traced
        grid axes, one vmap-of-vmap program per distinct (family, max_iter,
        fit_intercept) combo; fitted params stay device views."""
        if not grid:
            return []
        merged = [{**self.default_params, **self.params, **g} for g in grid]
        for p in merged:
            if p["family"] not in _FAMILIES:
                raise ValueError(f"Unknown GLM family {p['family']!r}")
            vp = float(p["variance_power"])
            if p["family"] == "tweedie" and not 1.0 < vp < 2.0:
                raise ValueError(
                    f"tweedie variance_power must be in (1, 2), got {vp}")
        k = int(X.shape[0])
        models: list[list] = [[None] * len(grid) for _ in range(k)]
        by_kw: dict[tuple, list[int]] = {}
        for i, p in enumerate(merged):
            by_kw.setdefault((p["family"], int(p["max_iter"]),
                              bool(p["fit_intercept"])), []).append(i)
        for (family, mi, fi), idxs in by_kw.items():
            rp = jnp.asarray([float(merged[i]["reg_param"]) for i in idxs],
                             jnp.float32)
            vp = jnp.asarray([float(merged[i]["variance_power"])
                              for i in idxs], jnp.float32)
            inner = lambda Xk, yk, wk, _f=family, _m=mi, _i=fi: jax.vmap(  # noqa: E731,E501
                lambda r, v: _train_glm(Xk, yk, wk, family=_f, max_iter=_m,
                                        fit_intercept=_i, reg_param=r,
                                        var_power=v))(rp, vp)
            betas, b0s = jax.vmap(inner)(X, y, w)  # [k, g, d], [k, g]
            for f in range(k):
                for j, i in enumerate(idxs):
                    models[f][i] = GLMModel(weights=betas[f, j],
                                            intercept=b0s[f, j],
                                            family=family)
        return models

    def grid_predict_scores_folds(self, models, X):
        """[k, G, n_va] mean predictions through the family link (None when
        grid points mix families — their links differ)."""
        if not models or not models[0]:
            return None
        fams = {m.family for row in models for m in row}
        if len(fams) != 1:
            return None
        family = fams.pop()
        W = jnp.stack([jnp.stack([jnp.asarray(m.weights, jnp.float32)
                                  for m in row]) for row in models])
        b = jnp.stack([jnp.stack([jnp.asarray(m.intercept, jnp.float32)
                                  for m in row]) for row in models])
        eta = jnp.einsum("knd,kgd->kgn", X, W) + b[:, :, None]
        if family == "gaussian":
            return eta
        if family == "binomial":
            return jax.nn.sigmoid(eta)
        return jnp.exp(eta)

    def grid_scores_folds_retained(self, X, y, w, grid, Xva,
                                   _n_classes=None):
        """One-sync dispatch unit: stacked scores plus the ``[k][G]``
        fitted-model nest retained as the warm-refit handle (model
        weights are device views of the stacked result). A subclass
        overriding ``grid_scores_folds`` keeps its semantics (delegate,
        no warm handle)."""
        if type(self).grid_scores_folds is not Predictor.grid_scores_folds:
            return super().grid_scores_folds_retained(
                X, y, w, grid, Xva, _n_classes=_n_classes)
        if not grid:
            return None, None
        models = self.grid_fit_arrays_folds(X, y, w, grid)
        if models is None:
            return None, None
        scores = self.grid_predict_scores_folds(models, Xva)
        if scores is None:
            return None, None
        return scores, models

    def supports_warm_refit(self) -> bool:
        return True

    def refit_winner(self, X, y, w, params, *, warm=None, lane=None,
                     hints=None):
        """Full-data refit warm-started from the fold-AVERAGED winning-
        lane coefficients through the donated-buffer program; cold
        ``fit_arrays`` (the serial path, bitwise) without a handle."""
        p = {**self.default_params, **self.params, **params}
        if warm is None or lane is None:
            return self.fit_arrays(X, y, w, p), False
        family = p["family"]
        if family not in _FAMILIES:
            raise ValueError(f"Unknown GLM family {family!r}")
        vp = float(p["variance_power"])
        if family == "tweedie" and not 1.0 < vp < 2.0:
            raise ValueError(
                f"tweedie variance_power must be in (1, 2), got {vp}")
        lane_models = [row[int(lane)] for row in warm]
        beta_init = jnp.mean(jnp.stack(
            [jnp.asarray(m.weights, jnp.float32) for m in lane_models]),
            axis=0)
        b_init = jnp.mean(jnp.stack(
            [jnp.asarray(m.intercept, jnp.float32) for m in lane_models]))
        beta, b0 = _glm_warm_program()(
            X, y, w, beta_init, b_init, family=family,
            max_iter=int(p["max_iter"]),
            fit_intercept=bool(p["fit_intercept"]),
            reg_param=jnp.float32(p["reg_param"]),
            var_power=jnp.float32(vp))
        return GLMModel(weights=np.asarray(beta), intercept=float(b0),
                        family=family), True


# ---------------------------------------------------------------------------
# Isotonic calibration
# ---------------------------------------------------------------------------

def _pav(x: np.ndarray, y: np.ndarray, w: np.ndarray
         ) -> tuple[np.ndarray, np.ndarray]:
    """Pool-adjacent-violators on (sorted-x, y, w); returns (x_knots, y_knots)."""
    order = np.argsort(x, kind="stable")
    xs, ys, ws = x[order], y[order].astype(float), w[order].astype(float)
    # pool
    vals, wts, xs_list = [], [], []
    for xi, yi, wi in zip(xs, ys, ws):
        vals.append(yi)
        wts.append(wi)
        xs_list.append(xi)
        while len(vals) > 1 and vals[-2] > vals[-1]:
            y2, w2 = vals.pop(), wts.pop()
            y1, w1 = vals.pop(), wts.pop()
            xs_list.pop()
            vals.append((y1 * w1 + y2 * w2) / (w1 + w2))
            wts.append(w1 + w2)
        # keep the x of the last element of each pool
    return np.asarray(xs_list[:len(vals)]), np.asarray(vals)


class IsotonicCalibratorModel(PredictionModel):
    """Calibrates the positive-class probability with the fitted isotonic
    step function (linear interpolation between knots)."""

    def __init__(self, x_knots=None, y_knots=None, uid: Optional[str] = None):
        self.x_knots = np.asarray(x_knots, np.float64) \
            if x_knots is not None else np.zeros(1)
        self.y_knots = np.asarray(y_knots, np.float64) \
            if y_knots is not None else np.zeros(1)
        super().__init__(uid=uid)

    def device_params(self):
        return (jnp.asarray(self.x_knots, jnp.float32),
                jnp.asarray(self.y_knots, jnp.float32))

    def device_apply(self, params, col: fr.PredictionColumn
                     ) -> fr.PredictionColumn:
        xk, yk = params
        score = col.probability[:, 1] if col.probability.shape[1] >= 2 \
            else col.prediction
        cal = jnp.interp(score, xk, yk)
        prob = jnp.stack([1.0 - cal, cal], axis=1)
        pred = (cal >= 0.5).astype(jnp.float32)
        return fr.PredictionColumn(pred, col.raw_prediction, prob)

    def transform_row(self, *values):
        pm = values[-1]
        score = pm.get("probability_1", pm.get("prediction", 0.0))
        cal = float(np.interp(score, self.x_knots, self.y_knots))
        return ft.Prediction.make(
            1.0 if cal >= 0.5 else 0.0,
            raw_prediction=pm_raw(pm), probability=[1.0 - cal, cal]).value

    def fitted_state(self):
        return {"x_knots": self.x_knots, "y_knots": self.y_knots}

    def set_fitted_state(self, state):
        self.x_knots = np.asarray(state["x_knots"], np.float64)
        self.y_knots = np.asarray(state["y_knots"], np.float64)

    def config(self):
        return {}

    @classmethod
    def from_config(cls, config, uid=None):
        return cls(uid=uid)


def pm_raw(pm: dict) -> list:
    out = []
    i = 0
    while f"rawPrediction_{i}" in pm:
        out.append(pm[f"rawPrediction_{i}"])
        i += 1
    return out


class IsotonicRegressionCalibrator(Estimator):
    """(label RealNN, Prediction) -> calibrated Prediction (reference
    ``IsotonicRegressionCalibrator`` wrapping Spark IsotonicRegression)."""

    in_types = (ft.RealNN, ft.Prediction)
    out_type = ft.Prediction

    def __init__(self, uid: Optional[str] = None):
        super().__init__(uid=uid)

    def fit_model(self, data):
        label_name, pred_name = self.input_names
        y = np.asarray(data.device_col(label_name).values, np.float64)
        pred_col = data.device_col(pred_name)
        prob = np.asarray(pred_col.probability)
        score = prob[:, 1] if prob.ndim == 2 and prob.shape[1] >= 2 \
            else np.asarray(pred_col.prediction)
        xk, yk = _pav(score, y, np.ones_like(y))
        return IsotonicCalibratorModel(x_knots=xk, y_knots=yk)
