"""Model step (train/steps.py, models/): model FLOPs of the train and eval
programs that ran in the traced window, over their spans on the device
timeline times the chip's bf16 peak, in percent. Nothing to read when
neither program ran in the window."""


def read(run):
    trace = run["trace"]
    if trace is None or run["peak"] is None:
        return None
    flops = seconds = 0.0
    for name, (runs, secs) in trace["programs"].items():
        flops += runs * run["flops"][name]
        seconds += secs
    if seconds <= 0:
        return None
    return 100.0 * flops / (seconds * run["peak"]["bf16_flops"])
