"""One key of the reduced profiler trace (`trace_reduce.reduce_events`)
over another: collective time or device time under no scope a
collection (`over="units"`), or the collectives' uncovered part as a
share of the traced window. A reduced trace that
lacks the key saw no such time: 0."""


def read(window: dict, key: str, over: str = "window_s",
         scale: float = 1.0):
    trace = window.get("trace")
    if not trace or not trace.get(over):
        return None
    return scale * trace.get(key, 0.0) / trace[over]
