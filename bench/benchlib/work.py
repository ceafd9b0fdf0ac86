"""The least work a Revolver superstep needs, from the graph and k alone.

A roofline share is this least time over the time measured. The byte counts
below are functions of |V| (n), the symmetrized edge count (ms, the slots of
the eq.-4 adjacency) and k only, so they read the same work whatever
implements the superstep, and no change of layout can push a share past
100%.

Edge phase (steps 3 and 5): every symmetrized edge has to be read once, as
its neighbor's index (ceil(log2 n) bits: no layout can name one of n
vertices in fewer) and its eq.-4 weight (1 or 2: one bit), and the labels
and lambdas of all vertices have to be read once (ceil(log2 k) bits each).
The [block, k] histograms it builds may stay on chip and are not counted.

LA update (steps 6-7): every vertex's k probabilities are state that lives
across supersteps, read and written once each, in the float32 the
configuration states; the new labels and lambdas are written once.

The operations are a few adds and multiplies per edge and per probability,
far below any compute peak, so the bytes bound the superstep.
"""
from __future__ import annotations

import math

F32 = 4


def _bits(x: int) -> int:
    return max(1, math.ceil(math.log2(x)))


def edge_phase_bytes(n: int, ms: int, k: int) -> float:
    """Least HBM bytes of a superstep's edge phase (steps 3 and 5)."""
    return ms * (_bits(n) + 1) / 8 + 2 * n * _bits(k) / 8


def la_update_bytes(n: int, k: int) -> float:
    """Least HBM bytes of a superstep's LA update (steps 6-7)."""
    return 2 * n * k * F32 + 2 * n * _bits(k) / 8


def superstep_bytes(n: int, ms: int, k: int) -> float:
    """Least HBM bytes a superstep moves."""
    return edge_phase_bytes(n, ms, k) + la_update_bytes(n, k)
