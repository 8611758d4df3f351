"""train_mfu: the model FLOPs of the traced steps over the device's busy time
in them times the chip's bf16 peak, in percent.

The FLOPs come from the configuration's reference module
(``flops_per_token``: forward and backward, no recomputation); busy time is
the union of the device operations' intervals in the traced window
(``bench.tracing``).  Nothing to read (no trace, no device operation): None.
"""


def read(ctx):
    red = ctx["reduced"]
    if red is None or red.busy_s <= 0:
        return None
    flops = ctx["flops_per_step"] * red.steps
    return 100.0 * flops / (red.busy_s * ctx["peaks"]["bf16_flops_per_s"])
