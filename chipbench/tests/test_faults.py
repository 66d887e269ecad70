"""Each cell, run on the CPU at a small size with the TPU check skipped:
sound, it comes out correct; with its timed path broken underneath, not
correct."""
from __future__ import annotations

import jax.numpy as jnp
import pytest

CELLS = ["deep96-exact.poisson", "deep96-scored.backlog",
         "deep96-exact.ingest"]


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(cell, name):
    out = cell(name)
    assert out["correct"] and out["failed"] == 0, out["checks"]
    assert list(out)[-1] == "checks"


def _altered_rows(monkeypatch):
    from repro.kernels import ops
    real = ops.packed_topk_masked

    def altered(*a, **k):
        vals, rows = real(*a, **k)
        return vals, jnp.where(rows >= 0, rows ^ 1, rows)
    monkeypatch.setattr(ops, "packed_topk_masked", altered)


def _altered_scores(monkeypatch):
    from repro.kernels import ops
    real = ops.fused_scored_topk_masked

    def altered(*a, **k):
        vals, rows = real(*a, **k)
        return vals + 0.5, rows
    monkeypatch.setattr(ops, "fused_scored_topk_masked", altered)


def _altered_score_rows(monkeypatch):
    from repro.kernels import ops
    real = ops.fused_scored_topk_masked

    def altered(*a, **k):
        vals, rows = real(*a, **k)
        return vals, jnp.where(rows >= 0, rows ^ 1, rows)
    monkeypatch.setattr(ops, "fused_scored_topk_masked", altered)


def _altered_words(monkeypatch):
    from repro.kernels import ops
    real = ops.encode_fused

    def altered(*a, **k):
        return real(*a, **k) ^ jnp.uint32(1)
    monkeypatch.setattr(ops, "encode_fused", altered)


def _half_answered(monkeypatch):
    from repro.serve.ann_service import AnnService
    real = AnnService.flush

    def half(self):
        out = real(self)
        return dict(list(out.items())[::2])
    monkeypatch.setattr(AnnService, "flush", half)


def _half_stored(monkeypatch):
    from repro.index.segment_log import SegmentLogStore
    real = SegmentLogStore.add_words

    def half(self, words, ids=None):
        n = words.shape[0] // 2
        return real(self, words[:n], None if ids is None else ids[:n])
    monkeypatch.setattr(SegmentLogStore, "add_words", half)


@pytest.mark.parametrize("name,fault", [
    ("deep96-exact.poisson", _altered_rows),
    ("deep96-exact.poisson", _half_answered),
    ("deep96-scored.backlog", _altered_scores),
    ("deep96-scored.backlog", _altered_score_rows),
    ("deep96-scored.backlog", _half_answered),
    ("deep96-exact.ingest", _altered_words),
    ("deep96-exact.ingest", _half_stored),
])
def test_broken_run_is_not_correct(cell, monkeypatch, name, fault):
    fault(monkeypatch)
    assert not cell(name)["correct"]
