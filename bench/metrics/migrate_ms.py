"""Device self time per superstep in the program's `migrate` phase scope:
steps 4 and 8 (the migration draw, the k-bin load scatter-adds). None where
the trace has no phases."""
from benchlib import phases


def read(rec):
    return phases.phase_ms(rec, "migrate")
