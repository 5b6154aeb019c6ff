"""Expert layer: (row, choice) pairs routed to this chip's experts per
decode step, summed over the expert layers; chunk calls' pairs are not
counted (the program counts decode calls' pairs apart)."""
from bench import readings


def read(run):
    if "decode_expert_rows" not in run.window.counters_after:
        return None
    steps = readings.counter_delta(run, "decode_steps")
    rows = readings.counter_delta(run, "decode_expert_rows")
    return rows / steps if steps else None
