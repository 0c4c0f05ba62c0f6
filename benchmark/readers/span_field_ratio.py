"""The sum of one field over the sum of another, times `scale`, across
the spans named `span` on the service's own ring (`GET /dump_traces`,
traced runs) that start inside the timed window: a share the program
counts span by span, such as the rows of a round that found their
key's table resident.

Parameters: `span`, `numerator`, `denominator` (names of the span's
fields), `scale`. Left out where no such span in the window carries
both fields, as with a program that does not record them, or where the
denominator's sum is 0.
"""


def read(ctx: dict, spec: dict):
    window = ctx["window"]
    start = window["wall_start"] * 1e9
    end = start + (window["t_end"] - window["t_start"]) * 1e9
    num = den = 0
    for s in ctx["spans"]:
        fields = s.get("fields") or {}
        if (
            s["name"] == spec["span"]
            and start <= s["t0_wall_ns"] <= end
            and spec["numerator"] in fields
            and spec["denominator"] in fields
        ):
            num += fields[spec["numerator"]]
            den += fields[spec["denominator"]]
    if not den:
        return None
    return spec["scale"] * num / den
