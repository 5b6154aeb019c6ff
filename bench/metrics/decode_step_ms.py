"""Engine: device time of one decode executable run, ms."""
from bench import readings


def read(run):
    return readings.step_ms(run, "decode")
