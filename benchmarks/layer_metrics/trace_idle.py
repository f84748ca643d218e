"""The device's idle share of the traced window, in per cent: 1 less
the union of the device-operation intervals over the window, averaged
over the chips used."""


def read(window: dict):
    trace = window.get("trace")
    if not trace or not trace["window_s"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
