"""Model step: useful model FLOPs of the tokens served in the window
(real prompt tokens and generated tokens, no padding or idle rows) over
the window and the chip's bf16 peak."""


def read(ctx):
    if not ctx["served_flops"] or not ctx["peak"]:
        return None
    return 100.0 * ctx["served_flops"] / ctx["seconds"] \
        / ctx["peak"]["bf16_flops_per_s"]
