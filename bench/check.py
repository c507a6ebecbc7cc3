"""The comparison that decides ``correct``, and the two precisions of the
plain reference.

``dot_highest`` is the precision the configurations state: f32 operands at
``Precision.HIGHEST``.  ``dot_3pass`` is the control's: the same product in
three bf16 passes (hi*hi + hi*lo + lo*hi, f32 accumulation), which is what
``Precision.HIGH`` does on a TPU, written out so that it means the same on
every backend.  It is the step below HIGHEST that would tempt a change.
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Check = Tuple[str, float, float]     # (name, value, limit)


def dot_highest(a, b):
    """f32 product at ``Precision.HIGHEST``, as the configurations state."""
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def dot_3pass(a, b):
    """f32 product in three bf16 passes: the control's precision."""
    # reduce_precision rounds to bf16 in f32 and is kept by the compiler,
    # where an f32 -> bf16 -> f32 round trip may be dropped as "excess
    # precision" (it was, on the TPU), which would leave lo = 0: one pass
    def split(x):
        hi = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
        lo = jax.lax.reduce_precision(x - hi, exponent_bits=8,
                                      mantissa_bits=7)
        return hi.astype(jnp.bfloat16), lo.astype(jnp.bfloat16)

    (ah, al), (bh, bl) = split(a), split(b)

    def d(x, y):
        return jnp.matmul(x, y, preferred_element_type=jnp.float32)

    return d(ah, bh) + (d(ah, bl) + d(al, bh))


DOTS = {"highest": dot_highest, "3pass": dot_3pass}


def reference(s, dot: str) -> List[np.ndarray]:
    """The configuration's plain reference over a loop state's whole graph
    (``s.src``, ``s.dst``, ``s.n_vertices``) on its features and weights,
    with the named dot: the outputs as host arrays."""
    src, dst = jax.device_put(s.src), jax.device_put(s.dst)
    f = jax.jit(lambda p, x, a, b: s.ctx.model.forward(
        p, x, a, b, n_vertices=s.n_vertices, n_layers=s.ctx.cfg["layers"],
        dot=DOTS[dot]))
    return [np.asarray(r) for r in f(s.params, s.x, src, dst)]


class MaxRelErr:
    """Running ``max |got - want| / max |want|`` over many output blocks,
    plus the count of blocks that are missing, misshapen or non-finite."""

    def __init__(self):
        self.max_abs = 0.0
        self.scale = 0.0
        self.bad = 0

    def add(self, got, want) -> None:
        """Compare one block of answers with the reference's."""
        want = np.asarray(want, np.float64)
        self.scale = max(self.scale, float(np.abs(want).max(initial=0.0)))
        if got is None:
            self.bad += 1
            return
        got = np.asarray(got, np.float64)
        if got.shape != want.shape or not np.isfinite(got).all():
            self.bad += 1
            return
        self.max_abs = max(self.max_abs,
                           float(np.abs(got - want).max(initial=0.0)))

    def value(self) -> float:
        """The largest error so far over the largest reference value."""
        return self.max_abs / max(self.scale, 1e-30)


def checks(err: MaxRelErr, limits: Dict) -> List[Check]:
    """The numbers compared, each beside its limit (from
    ``bench/limits/<cell>.json``)."""
    return [("rel_err", err.value(), float(limits["rel_err"])),
            ("bad_outputs", float(err.bad), float(limits["bad_outputs"]))]


def passed(cs: Iterable[Check]) -> bool:
    """Every compared number is within its limit."""
    return all(v <= lim for _, v, lim in cs)


def fmt(cs: Sequence[Check]) -> List[str]:
    """One line per compared number, beside its limit."""
    return [f"check {name}: {value!r} (limit {limit!r})"
            for name, value, limit in cs]
