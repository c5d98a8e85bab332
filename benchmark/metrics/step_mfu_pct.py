"""The whole step's share of the chip's bf16 peak, from the trace: the
executions of the trainer's jitted step among the traced steps x batch x
the configuration's ``train_flops_per_image`` (forward and both backward
products of every conv and dense layer, from shapes; see
``benchmark/flops.py``) over the traced span x chips x peak.

The traced span runs from the first device operation to the last, idle
gaps included, so this is the device's time and not the tracer's: the host
window of a traced run also holds seconds of starting and stopping the
trace, which no step sees."""


def read(run: dict):
    flops = run["config"].get("train_flops_per_image")
    trace = run["trace"]
    if not flops or not trace or not trace.get("step_executions"):
        return None
    if not trace["window_s"]:
        return None
    work = trace["step_executions"] * run["config"]["batch_size"] * flops
    peak = run["peaks"]["bf16_flops_per_s"] * run["chips"]
    return 100.0 * work / (trace["window_s"] * peak)
