"""The device's idle share of the traced span, in per cent: 1 minus the
union of its operations' intervals over the span's length."""


def read(ctx: dict, spec: dict):
    trace = ctx["trace"]
    if not trace or not trace["window_s"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
