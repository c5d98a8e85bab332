"""Device-busy time per executed train step in the traced steps: the union
of the device's operation intervals over the number of executions of the
trainer's jitted step module among them."""


def read(run: dict):
    trace = run["trace"]
    if not trace or not trace.get("step_executions"):
        return None
    return 1e3 * trace["busy_s"] / trace["step_executions"]
