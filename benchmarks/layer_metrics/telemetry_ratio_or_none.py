"""`telemetry_ratio` for counters that a program may not have yet: a
window whose summaries lack a key has nothing to read there, so the
metric is left out (the parent commit of the PR that adds a counter is
traced with that PR's metric files)."""

from benchmarks.layer_metrics import telemetry_ratio


def read(window: dict, num: str, den: str):
    try:
        return telemetry_ratio.read(window, num, den)
    except KeyError:
        return None
