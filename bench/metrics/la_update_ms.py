"""Device self time per superstep in the program's `la-update` phase scope:
steps 6-7 (the learning automata's probability update). None where the
trace has no phases."""
from benchlib import phases


def read(rec):
    return phases.phase_ms(rec, "la-update")
