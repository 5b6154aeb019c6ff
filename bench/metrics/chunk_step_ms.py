"""Engine: device time of one chunk-prefill executable run, ms."""
from bench import readings


def read(run):
    return readings.step_ms(run, "chunk")
