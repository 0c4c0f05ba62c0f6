"""Process start -> start of the timed window: service start, program
loads or compiles, table builds, the pool, warm-up."""


def read(ctx: dict, spec: dict):
    return ctx["setup_s"]
