"""Pure-jnp oracle for the fused unique-and-compact frontier op.

Single-pass replacement for the pair ``unique_padded(cat, cap)`` +
``lookup(uniq, cat)``: one key-value sort of the concatenated frontier,
first-occurrence flags, cumulative ranks, and two scatters — the
reference plan backend's own
:func:`repro.core.frontier.sort_unique_with_inverse`.  Bit-identical to
the reference pair:

* ``uniq`` equals ``jnp.unique(cat, size=cap, fill_value=INVALID)`` —
  INVALID participates as an ordinary value that sorts last, and
  overflow keeps the smallest ``cap`` uniques;
* ``inv[j]`` equals ``lookup(uniq, cat[j])`` — the position of ``cat[j]``
  in ``uniq``, or -1 when ``cat[j]`` is INVALID or was dropped by the
  overflow policy (rank >= cap).

Used directly on non-TPU backends and as the test oracle for the Pallas
kernel (`repro.kernels.unique_compact.kernel`).
"""
from __future__ import annotations

from repro.core.frontier import sort_unique_with_inverse as unique_with_inverse_ref

__all__ = ["unique_with_inverse_ref"]
