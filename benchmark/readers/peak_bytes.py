"""`peak_bytes_in_use` of the service's device after the window, as
`memory_stats()` reports it in the service dump. Left out where the
backend keeps no memory statistics."""


def read(ctx: dict, spec: dict):
    return ctx["service"].get("peak_bytes_in_use")
