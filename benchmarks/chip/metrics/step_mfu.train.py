"""Model FLOP utilization of the whole train step, in % of the chips' bf16
peak: forward plus backward (3x the forward) model FLOPs of the window's
real tokens, with no recomputation counted, over window x chips x peak.
Against the bf16 peak whatever the compute precision.  Nothing to read on
a device with no peak in ``peaks.json`` (the CPU of a rehearsal)."""


def read(run: dict):
    if run["peak"] is None:
        return None
    w = run["window"]
    return 100.0 * 3.0 * w["model_flops"] / (w["seconds"] * run["chips"] * run["peak"]["bf16_flops"])
