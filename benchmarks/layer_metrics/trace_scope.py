"""Device time under one named scope of the program, from the reduced
profiler trace: seconds per traced unit (iteration, chunk or compiled
call), averaged over the chips used."""


def read(window: dict, scope: str, per_unit: bool = True):
    trace = window.get("trace")
    if not trace or scope not in trace["scopes"]:
        return None
    seconds = trace["scopes"][scope]
    if per_unit:
        if not trace.get("units"):
            return None
        seconds /= trace["units"]
    return seconds
