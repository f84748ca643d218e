"""A statistic over the window's telemetry summaries of one key that is
not a count (the straggler ratio: max over mean of per-lane loop
iterations)."""

from benchmarks.harness import stat as _stat


def read(window: dict, key: str, stat: str = "max"):
    return _stat([s[key] for s in window.get("telemetry") or []
                  if key in s], stat)
