"""A statistic of one key of the trainer's runlog `scalars` records of
the window (host clock around `block_until_ready`, or a count)."""

from benchmarks.harness import stat as _stat


def read(window: dict, key: str, stat: str = "median"):
    values = [s[key] for s in window.get("scalars", []) if key in s]
    return _stat(values, stat)
