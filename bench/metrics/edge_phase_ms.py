"""Device self time per superstep in the program's `edge-phase` phase
scope: steps 3 and 5 (the vertex-state gathers, the [block, k] histograms,
scores, argmax). None where the trace has no phases."""
from benchlib import phases


def read(rec):
    return phases.phase_ms(rec, "edge-phase")
