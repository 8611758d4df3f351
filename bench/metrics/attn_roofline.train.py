"""attn_roofline.train: causal attention's model FLOPs in the traced steps
over the device self time under the program's ``attention_core`` scope at
the chip's bf16 peak, in percent (``bench.program_trace.attn_roofline``).

The work is the reference's attention term (q·k and p·v, forward and
backward: ``attention_flops_per_token``), whatever implements attention, so
a recomputed forward adds time and no work.  The share is of the FLOP
bound alone: at 2048 positions attention does some 800 FLOPs a byte of
q, k, v and o, above a TPU v5e's ridge of 240; a cell of short sequences,
which bytes would bound, needs its bytes counted too.  Nothing to read (no
program trace, no attention term in the reference, no operation under
``attention_core``): None.
"""
from bench import program_trace


def read(ctx):
    pt = ctx["program"]
    if pt is None:
        return None
    per_token = program_trace.attention_flops_per_token(
        ctx["ref"], ctx["config"], ctx["traffic"]["seq_len"])
    if per_token is None:
        return None
    return program_trace.attn_roofline(
        pt, per_token * ctx["tokens_per_step"],
        ctx["peaks"]["bf16_flops_per_s"])
