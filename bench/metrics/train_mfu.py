"""Model FLOP utilization of the traced training window, in %: model FLOPs
per optimizer step (bench/flops.py) times optimizer steps per second, over
chips times the chip's bf16 peak (bench/peaks.json)."""


def read(run):
    peaks = run.get("peaks")
    if not peaks or not run.get("steps_per_s"):
        return None
    return (100.0 * run["flops_per_step"] * run["steps_per_s"]
            / (run["chips"] * peaks["bf16_flops_per_s"]))
