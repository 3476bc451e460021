"""Share of the chips' bf16 peak that useful training work makes over the
traced window (its rounds drained at both ends): useful tokens x analytic
forward+backward FLOPs per token (``flops/<family>.py``, no
recomputation) over its seconds x chips x peak (``peaks.json``)."""


def read(rec):
    flops = rec["tokens"] * rec["flops_per_token"]
    return 100.0 * flops / (rec["window_s"] * rec["chips"]
                            * rec["peak_flops"])
