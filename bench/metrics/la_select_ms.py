"""Device self time per superstep in the program's `la-select` phase scope:
the Revolver rule's steps 1-2 (key split, action draw, demand scatter-add,
migration probabilities). None where the trace has no phases."""
from benchlib import phases


def read(rec):
    return phases.phase_ms(rec, "la-select")
