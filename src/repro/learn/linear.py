"""L2 linear classifiers trained directly on packed codes (paper §6).

The paper trains L2-regularized linear SVMs (LIBLINEAR) on the one-hot
expansion of the codes. This module keeps the objective family —

    min_W  0.5 ||W||^2 + C * sum_i loss(y_i, margin_i)

with squared-hinge (LIBLINEAR's L2R_L2LOSS primal) or logistic loss,
solved by Adam with cosine decay — but replaces the feature matrix with
the packed words themselves:

* **margins** are per-projection weight-table gathers
  (``kernels.packed_linear`` forward; the one-hot matrix never exists),
  row normalization folded in as the scalar ``fspec.scale`` pre-scale;
* **gradients** scatter per-example contributions straight back into
  the [k, 2^b] tables (fused backward kernel), multiplied by
  ``fspec.entry_mask()`` so phantom table columns (packing padding)
  never learn — with zero init they stay exactly zero, keeping packed
  L2/margins/gradients equal to the dense ``expand_codes`` path up to
  float rounding;
* **tombstones**: the masked kernel variants + a live-row mask on the
  loss terms let the same step run over a churned ``SegmentLogStore``
  segment, dead rows contributing exactly nothing.

``train_dense_linear`` is the dense twin (autodiff over an explicit
feature matrix) — the parity oracle, and the engine behind the
``core.svm`` compat wrappers. Streaming/minibatch/sharded training
lives in ``learn.trainer``.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import packing as _packing
from repro.kernels import ops as _ops
from repro.learn.features import PackedFeatureSpec

__all__ = ["LearnConfig", "PackedLinearModel", "packed_margins",
           "packed_data_grads", "packed_loss_and_grads", "targets_pm",
           "adam_update", "adam_cosine_train", "full_batch_fit",
           "train_packed_linear", "train_dense_linear"]

_LOSSES = ("sq_hinge", "logistic")


@dataclass(frozen=True)
class LearnConfig:
    """Static training knobs (one jit cache entry per distinct config)."""
    loss: str = "sq_hinge"   # sq_hinge | logistic
    c: float = 1.0           # data-loss tradeoff (LIBLINEAR's C)
    steps: int = 400         # optimizer steps (full-batch or minibatch)
    lr: float = 0.1          # peak Adam lr (cosine-decayed to 0)
    batch: int = 0           # minibatch rows; 0 = full batch
    seed: int = 0            # batch-sampling seed (minibatch path)
    impl: str = "auto"       # kernel dispatch (see kernels.ops)

    def __post_init__(self):
        if self.loss not in _LOSSES:
            raise ValueError(f"loss must be one of {_LOSSES}, "
                             f"got {self.loss!r}")


def targets_pm(y, n_outputs: int):
    """Labels -> ±1 target matrix [C, n].

    n_outputs == 1 (binary): y [n] in {-1, +1} passes through as
    [1, n]. n_outputs > 1 (one-vs-rest): y [n] int class ids in
    [0, n_outputs) become +1 at the true class row, -1 elsewhere.
    """
    y = jnp.asarray(y)
    if n_outputs == 1:
        return y.astype(jnp.float32)[None, :]
    cls = jnp.arange(n_outputs)[:, None]
    return jnp.where(y[None, :] == cls, 1.0, -1.0).astype(jnp.float32)


def _loss_and_margin_grad(margins, y_pm, c: float, loss: str, live=None):
    """Data term of the objective and its margin gradient.

    margins/y_pm float32 [C, n]; ``live`` optional bool [n] — dead rows
    contribute zero loss and zero gradient. Returns (scalar loss_sum,
    g [C, n] = dloss/dmargin).
    """
    if loss == "sq_hinge":
        h = jnp.maximum(0.0, 1.0 - y_pm * margins)
        if live is not None:
            h = jnp.where(live[None, :], h, 0.0)
        return c * jnp.sum(h * h), (-2.0 * c) * (y_pm * h)
    z = -y_pm * margins
    ll = jax.nn.softplus(z)
    s = jax.nn.sigmoid(z)
    if live is not None:
        ll = jnp.where(live[None, :], ll, 0.0)
        s = jnp.where(live[None, :], s, 0.0)
    return c * jnp.sum(ll), -c * (y_pm * s)


def packed_margins(tables, bias, words, fspec: PackedFeatureSpec,
                   valid_words=None, impl: str = "auto"):
    """Model margins on packed rows: tables f32 [C, F*P], bias f32 [C],
    words uint32 [n, W] -> f32 [C, n] = scale * gather-sum + bias.

    With ``valid_words`` (packed row-validity bitmask) the masked
    forward kernel runs instead; dead rows come back as bias alone —
    meaningless, and excluded from every loss by the same mask.
    """
    if valid_words is None:
        raw = _ops.packed_linear_fwd(tables, words, fspec.bits, impl=impl)
    else:
        raw = _ops.packed_linear_fwd_masked(tables, words, valid_words,
                                            fspec.bits, impl=impl)
    return raw * fspec.scale + bias[:, None]


def packed_data_grads(params, words, y_pm, fspec: PackedFeatureSpec,
                      c: float = 1.0, loss: str = "sq_hinge",
                      valid_words=None, impl: str = "auto"):
    """Data term of the objective + its gradients on one packed block.

    params = (tables f32 [C, F*P], bias f32 [C]); y_pm ±1 targets
    [C, n] (``targets_pm``). Returns (data_loss, (dTables, dBias)): the
    per-example contributions scattered through the fused backward
    kernel, scaled by ``fspec.scale``, phantom columns masked — **no
    L2 term**, so multi-part callers (segment loops, sharded shards)
    can sum blocks and add the regularizer exactly once.
    """
    tables, bias = params
    m = packed_margins(tables, bias, words, fspec, valid_words, impl)
    live = (None if valid_words is None
            else _packing.unpack_bitmask(valid_words, words.shape[0]))
    data_loss, g = _loss_and_margin_grad(m, y_pm, c, loss, live)
    # without the barrier a TPU compiler fuses the bias sum into whatever
    # computed the margins, and picks its summation order per fusion: a
    # kernel forward and the oracle forward then give different bias
    # gradients from bit-identical g
    g = jax.lax.optimization_barrier(g)
    if valid_words is None:
        dt = _ops.packed_linear_bwd(g, words, fspec.bits, impl=impl)
    else:
        dt = _ops.packed_linear_bwd_masked(g, words, valid_words,
                                           fspec.bits, impl=impl)
    dt = dt * (fspec.scale * fspec.entry_mask())
    return data_loss, (dt, jnp.sum(g, axis=1))


def packed_loss_and_grads(params, words, y_pm, fspec: PackedFeatureSpec,
                          c: float = 1.0, loss: str = "sq_hinge",
                          valid_words=None, impl: str = "auto"):
    """One full objective + gradient evaluation on packed rows:
    ``packed_data_grads`` plus the L2 term (tables regularized, bias
    free — LIBLINEAR's convention). Returns (loss, (dTables, dBias))."""
    tables, bias = params
    data_loss, (dt, db) = packed_data_grads(params, words, y_pm, fspec,
                                            c, loss, valid_words, impl)
    return (0.5 * jnp.sum(tables * tables) + data_loss,
            (dt + tables, db))


def adam_update(params, m, v, g, i, steps: int, lr: float):
    """One Adam step with cosine decay — THE update rule (single source
    of truth for the full-batch scan, the minibatch per-step executable
    and the dense compat path; bit-identical to the original
    ``core.svm`` solver). ``i`` is the float32 step index (traced, so
    step counts never recompile). Returns (params, m, v).
    """
    lr_i = lr * 0.5 * (1.0 + jnp.cos(jnp.pi * i / steps))
    b1, b2, eps = 0.9, 0.999, 1e-8
    m = jax.tree.map(lambda mm, gg: b1 * mm + (1 - b1) * gg, m, g)
    v = jax.tree.map(lambda vv, gg: b2 * vv + (1 - b2) * gg * gg, v, g)
    t = i + 1.0

    def upd(p, mm, vv):
        mh = mm / (1 - b1 ** t)
        vh = vv / (1 - b2 ** t)
        return p - lr_i * mh / (jnp.sqrt(vh) + eps)

    return jax.tree.map(upd, params, m, v), m, v


def adam_cosine_train(params, grad_fn, steps: int, lr: float):
    """Full-batch Adam with cosine decay (deterministic; the trainer
    shared by the dense and packed paths): ``adam_update`` scanned over
    ``steps``.

    params: pytree of float arrays; grad_fn(params) -> matching grads.
    """
    zeros = jax.tree.map(jnp.zeros_like, params)

    def step(carry, i):
        params, m, v = carry
        return adam_update(params, m, v, grad_fn(params), i, steps, lr), None

    (params, _, _), _ = jax.lax.scan(
        step, (params, zeros, zeros), jnp.arange(steps, dtype=jnp.float32))
    return params


@dataclass
class PackedLinearModel:
    """A trained linear classifier living in weight-table space.

    tables f32 [C, F*P] (flat ``PackedFeatureSpec`` layout, phantom
    columns zero), bias f32 [C]. C == 1 is a binary model over ±1
    labels; C > 1 is one-vs-rest over int class ids (predict = argmax).
    """
    fspec: PackedFeatureSpec
    tables: jax.Array
    bias: jax.Array
    loss: str = "sq_hinge"

    @classmethod
    def zeros(cls, fspec: PackedFeatureSpec, n_outputs: int = 1,
              loss: str = "sq_hinge") -> "PackedLinearModel":
        """Zero-initialized model (the training start point)."""
        return cls(fspec=fspec,
                   tables=jnp.zeros((n_outputs, fspec.table_width),
                                    jnp.float32),
                   bias=jnp.zeros((n_outputs,), jnp.float32), loss=loss)

    @property
    def n_outputs(self) -> int:
        """Margin rows: 1 for binary, n_classes for one-vs-rest."""
        return self.tables.shape[0]

    def margins(self, words, valid_words=None, impl: str = "auto"):
        """Packed rows [n, W] -> margins f32 [C, n] (fused forward)."""
        return packed_margins(self.tables, self.bias, words, self.fspec,
                              valid_words, impl)

    def decision(self, words, impl: str = "auto"):
        """Binary decision values f32 [n] (requires C == 1)."""
        if self.n_outputs != 1:
            raise ValueError("decision() is binary-only; use margins()")
        return self.margins(words, impl=impl)[0]

    def predict_from_margins(self, margins):
        """Margins [C, n] -> labels [n]: ±1 (binary, zero margin -> +1)
        or int class ids (one-vs-rest argmax)."""
        if self.n_outputs == 1:
            return jnp.where(margins[0] >= 0, 1, -1).astype(jnp.int32)
        return jnp.argmax(margins, axis=0).astype(jnp.int32)

    def predict(self, words, impl: str = "auto"):
        """Predicted labels [n] (``predict_from_margins`` of a fused
        forward pass)."""
        return self.predict_from_margins(self.margins(words, impl=impl))

    def accuracy(self, words, y, impl: str = "auto") -> float:
        """Mean prediction accuracy against labels ``y`` (±1 binary or
        int class ids, matching ``predict``)."""
        pred = np.asarray(self.predict(words, impl=impl))
        return float(np.mean(pred == np.asarray(y)))

    def dense_weights(self):
        """Weights in the dense ``expand_codes`` layout f32
        [C, k*n_codes] (phantom columns dropped) — parity/debug view."""
        return self.fspec.dense_from_tables(self.tables)


def full_batch_fit(words, y_pm, fspec: PackedFeatureSpec,
                   cfg: LearnConfig, valid_words=None, grad_fn=None):
    """Shared full-batch driver: zero init + the whole Adam scan under
    one donated jit (weight and optimizer buffers update in place).

    y_pm: ±1 targets [C, n] (``targets_pm``). ``grad_fn(params) ->
    grads`` overrides the default unsharded gradient — how the trainer
    plugs in the ``shard_map`` data-parallel path. Returns (tables,
    bias).
    """
    init = (jnp.zeros((y_pm.shape[0], fspec.table_width), jnp.float32),
            jnp.zeros((y_pm.shape[0],), jnp.float32))
    if grad_fn is None:
        def grad_fn(p):
            return packed_loss_and_grads(
                p, words, y_pm, fspec, c=cfg.c, loss=cfg.loss,
                valid_words=valid_words, impl=cfg.impl)[1]

    def run(params):
        return adam_cosine_train(params, grad_fn, cfg.steps, cfg.lr)

    return jax.jit(run, donate_argnums=(0,))(init)


def train_packed_linear(words, y, fspec: PackedFeatureSpec,
                        cfg: LearnConfig = LearnConfig(), *,
                        valid_words=None,
                        n_outputs: int = 1) -> PackedLinearModel:
    """Full-batch training directly on packed rows.

    words uint32 [n, W]; y ±1 [n] (binary) or int class ids
    (n_outputs > 1); ``valid_words`` optional packed validity bitmask —
    tombstoned rows contribute nothing (``full_batch_fit`` under the
    hood). Minibatch/streaming/sharded variants: ``learn.trainer``.
    """
    tables, bias = full_batch_fit(words, targets_pm(y, n_outputs), fspec,
                                  cfg, valid_words=valid_words)
    return PackedLinearModel(fspec=fspec, tables=tables, bias=bias,
                             loss=cfg.loss)


def _dense_objective(params, x, y, c: float, loss: str):
    w, b = params
    margin = y * (x @ w + b)
    if loss == "sq_hinge":
        hinge = jnp.maximum(0.0, 1.0 - margin)
        return 0.5 * jnp.sum(w * w) + c * jnp.sum(hinge * hinge)
    return 0.5 * jnp.sum(w * w) + c * jnp.sum(jax.nn.softplus(-margin))


def train_dense_linear(x, y, cfg: LearnConfig = LearnConfig(),
                       x_val: Optional[jnp.ndarray] = None,
                       y_val: Optional[jnp.ndarray] = None):
    """Dense-feature twin of ``train_packed_linear``: binary L2 linear
    classifier by autodiff over an explicit feature matrix x [n, d],
    y ±1 [n]. Returns (w [d], b). Identical optimizer trajectory to the
    packed path up to float rounding — the parity oracle, and the
    engine behind ``core.svm.train_linear_svm`` (x_val/y_val accepted
    for that signature, unused)."""
    del x_val, y_val
    n, d = x.shape
    params = (jnp.zeros((d,), jnp.float32), jnp.zeros((), jnp.float32))
    grad_obj = jax.grad(_dense_objective)

    def grad_fn(p):
        return grad_obj(p, x, y, cfg.c, cfg.loss)

    return adam_cosine_train(params, grad_fn, cfg.steps, cfg.lr)
