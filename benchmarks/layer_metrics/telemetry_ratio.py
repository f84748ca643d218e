"""A ratio of two of the engine's on-device counters over the window
(`obs/telemetry.summarize`; a key may be dotted, and several summaries,
one per iteration, are summed first). A metric over a counter that a
program may not have yet says so in its data file (`"may_lack": true`):
a window whose summaries lack a key has nothing to read there, so the
metric is left out (the parent commit of the PR that adds a counter is
traced with that PR's metric files). Without the key a missing counter
is an error: a typing slip must not read as "nothing to read"."""


def _get(summary: dict, dotted: str):
    for part in dotted.split("."):
        summary = summary[part]
    return summary


def read(window: dict, num: str, den: str, may_lack: bool = False):
    summaries = window.get("telemetry") or []
    try:
        n = sum(_get(s, num) for s in summaries)
        d = sum(_get(s, den) for s in summaries)
    except KeyError:
        if may_lack:
            return None
        raise
    return n / d if d else None
