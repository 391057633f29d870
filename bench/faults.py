"""Faults planted in the timed path, to show that ``correct`` catches
them: each is a context manager that breaks one program entry point the
drivers call while it is active.

* ``unchanged_step`` - an EM iteration returns the model it was given;
* ``half_batch`` - an EM iteration sees only the first half of the
  utterances (at rest: half the statistics, with S halved to match);
  a serving batch computes only the first half of its requests (none
  of a batch of one), the rest come back as zero vectors;
* ``altered_answer`` - the first i-vector of every served batch is
  changed where it is produced (its coordinates rolled by one).

One-chip cells exchange nothing between chips, so the fault of an
exchange left out does not apply to them.
"""
from __future__ import annotations

import contextlib

import numpy as np

FAULTS = ("unchanged_step", "half_batch", "altered_answer")


@contextlib.contextmanager
def _patched(obj, name, make):
    orig = getattr(obj, name)
    setattr(obj, name, make(orig))
    try:
        yield
    finally:
        setattr(obj, name, orig)


@contextlib.contextmanager
def unchanged_step():
    from repro.core import trainer as TR

    def it(orig):
        def make(cfg, mesh=None):
            fn = orig(cfg, mesh)
            return lambda model, ubm, feats, mask=None: (
                (model,) + tuple(fn(model, ubm, feats, mask)[1:]))
        return make

    def em(orig):
        def make(cfg):
            fn = orig(cfg)
            return lambda model, n, f, S: (model, fn(model, n, f, S)[1])
        return make

    with _patched(TR, "make_iter_fn", it), _patched(TR, "make_em_fn", em):
        yield


@contextlib.contextmanager
def half_batch():
    from repro.core import trainer as TR
    from repro.serving.extractor import IVectorExtractor

    def it(orig):
        def make(cfg, mesh=None):
            fn = orig(cfg, mesh)

            def step(model, ubm, feats, mask=None):
                h = feats.shape[0] // 2
                return fn(model, ubm, feats[:h],
                          None if mask is None else mask[:h])
            return step
        return make

    def em(orig):
        def make(cfg):
            fn = orig(cfg)

            def step(model, n, f, S):
                h = n.shape[0] // 2
                return fn(model, n[:h], f[:h], None if S is None else S / 2)
            return step
        return make

    def batch(orig):
        def run(self, feats, mask):
            real = int(np.sum(np.asarray(mask).sum(axis=1) > 0))
            return orig(self, feats, mask.at[real // 2:].set(0.0))
        return run

    with _patched(TR, "make_iter_fn", it), _patched(TR, "make_em_fn", em), \
            _patched(IVectorExtractor, "_run_batch", batch):
        yield


@contextlib.contextmanager
def altered_answer():
    from repro.serving.extractor import IVectorExtractor

    def batch(orig):
        def run(self, feats, mask):
            out = np.array(orig(self, feats, mask))
            out[0] = np.roll(out[0], 1)
            return out
        return run

    with _patched(IVectorExtractor, "_run_batch", batch):
        yield


def planted(name: str):
    """The context manager of fault ``name`` (one of FAULTS)."""
    return {"unchanged_step": unchanged_step, "half_batch": half_batch,
            "altered_answer": altered_answer}[name]()
