"""Process start to the first batch of the window: imports, the pool, the
trainer's build, compilation (or the cache), the first checked steps."""


def read(run: dict):
    return run["to_window_s"]
