"""A fitted ``TreeEnsembleModel``'s score re-done in NumPy float32, for the
tests that hold the program to ``base_score + learning_rate * sum(tree
outputs)``: binning by ``searchsorted``, the level-wise walk of every tree,
the rounds summed one after another. And the plain forms of the two
lookups the scoring path makes (``bin_codes``, ``gather_walk``), which the
program's compare-and-select forms are held to, code for code and bit for
bit."""

import numpy as np

import jax.numpy as jnp

from transmogrifai_tpu import frame as fr
from transmogrifai_tpu.dag import fuse_dag_program
from transmogrifai_tpu.features.builder import FeatureBuilder
from transmogrifai_tpu.types import feature_types as ft


def bin_codes(X: np.ndarray, edges: np.ndarray) -> np.ndarray:
    """``[n, d]`` codes: ``np.searchsorted(edges[j], X[:, j], "left")``."""
    return np.stack([np.searchsorted(edges[j], X[:, j], side="left")
                     for j in range(X.shape[1])], axis=1)


def gather_walk(Xb, feats, bins, leaf_values):
    """``predict_tree`` as it was before its lookups compared against whole
    tables: three row-long gathers a level and one for the leaf."""
    rows = jnp.arange(Xb.shape[0])
    node = jnp.zeros(Xb.shape[0], dtype=jnp.int32)
    for f_tab, b_tab in zip(feats, bins):
        f, b = f_tab[node], b_tab[node]
        left = jnp.where(f < 0, True, Xb[rows, jnp.clip(f, 0)] <= b)
        node = node * 2 + jnp.where(left, 0, 1).astype(jnp.int32)
    return leaf_values[node]


def tree_outputs(model, X: np.ndarray) -> np.ndarray:
    """``[rounds, n_out, n]`` float32: the leaf each tree gives each row."""
    edges = np.asarray(model.bin_edges, np.float32)
    Xb = bin_codes(X, edges)
    feats, bins, leaves = model.trees
    feats = [np.asarray(f) for f in feats]
    bins = [np.asarray(b) for b in bins]
    leaves = np.asarray(leaves, np.float32)
    rounds, n_out = leaves.shape[:2]
    rows = np.arange(len(X))
    out = np.zeros((rounds, n_out, len(X)), np.float32)
    for r in range(rounds):
        for k in range(n_out):
            node = np.zeros(len(X), np.int64)
            for f_l, b_l in zip(feats, bins):
                f, b = f_l[r, k][node], b_l[r, k][node]
                left = (f < 0) | (Xb[rows, np.clip(f, 0, None)] <= b)
                node = node * 2 + np.where(left, 0, 1)
            out[r, k] = leaves[r, k][node]
    return out


def margins(model, X: np.ndarray) -> tuple:
    """``base + rate * sum`` as ``[n, n_out]`` float32, twice: with the
    product rounded before the addition, and with one rounding for both
    (the fused multiply-add XLA:CPU emits; the product of two float32 is
    exact in float64). A compiler may choose either for each element."""
    outs = tree_outputs(model, X)
    total = np.zeros(outs.shape[1:], np.float32)
    for out in outs:
        total = total + out
    base = np.asarray(model.base_score, np.float32)
    rate = np.float32(model.learning_rate)
    two_roundings = base + rate * total.T
    one_rounding = (base.astype(np.float64) + np.float64(rate)
                    * total.T.astype(np.float64)).astype(np.float32)
    return two_roundings, one_rounding


def assert_is_margin(got, model, X: np.ndarray) -> None:
    """``got`` ([n, n_out]) is ``margins`` to the bit, element by element."""
    two, one = margins(model, X)
    got = np.asarray(got)
    assert got.dtype == np.float32 and got.shape == two.shape
    off = ~((got == two) | (got == one))
    assert not off.any(), (int(off.sum()), got[off][:3], two[off][:3])


def margin_of(pred: fr.PredictionColumn, model) -> np.ndarray:
    """The ensemble's ``[n, n_out]`` margin as the prediction column holds
    it: a regressor's prediction, a boosted classifier's raw score."""
    if not model.is_classifier:
        return np.asarray(pred.prediction)[:, None]
    raw = np.asarray(pred.raw_prediction)
    return raw[:, 1:] if model.n_out == 1 else raw


def fused_predict(model, X: np.ndarray, precision: str = "f32"):
    """``model`` scored by the fused DAG program of one layer at a rung, as
    serving runs it: quantized parameters on the rungs below f32."""
    if not model.input_features:   # wire it once: the program asks names
        feats = FeatureBuilder.from_frame(fr.HostFrame.from_dict({
            "label": (ft.RealNN, np.zeros(1)),
            "vec": (ft.OPVector, [[0.0] * X.shape[1]])}), response="label")
        feats["label"].transform_with(model, feats["vec"])
    params = (model.device_params() if precision == "f32"
              else model.quantize_device_params(precision))
    prog = fuse_dag_program([[model]], precision=precision)
    vec_name = model.runtime_input_names()[0]
    out = prog({model.uid: params}, {},
               {vec_name: fr.VectorColumn(jnp.asarray(X))})
    return out[model.get_output().name]


def assert_rung_adds_base_unrounded(model, X: np.ndarray, rung: str) -> None:
    """On a rung below f32 the margin is float32(base) + the rung's own
    ``learning_rate * sum``, which the same trees under a zero base give."""
    zero = type(model).from_config({**model.config(), "base_score": 0.0})
    zero.set_fitted_state(model.fitted_state())
    got = margin_of(fused_predict(model, X, rung), model)
    rest = margin_of(fused_predict(zero, X, rung), zero)
    assert got.dtype == np.float32
    np.testing.assert_array_equal(
        got, np.asarray(model.base_score, np.float32) + rest)
