"""A ratio of two of the engine's on-device counters over the window
(`obs/telemetry.summarize`; a key may be dotted, and several summaries,
one per iteration, are summed first)."""


def _get(summary: dict, dotted: str):
    for part in dotted.split("."):
        summary = summary[part]
    return summary


def read(window: dict, num: str, den: str):
    summaries = window.get("telemetry") or []
    n = sum(_get(s, num) for s in summaries)
    d = sum(_get(s, den) for s in summaries)
    return n / d if d else None
