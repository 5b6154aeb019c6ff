"""Device: share of the traced window with no operation running, %."""
from bench import readings


def read(run):
    return readings.device_idle_pct(run)
