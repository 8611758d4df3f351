"""Names of the ``jax.named_scope`` regions round the model's device work.

JAX writes the scopes open at each traced operation into the
``op_name`` metadata of the HLO it lowers to, through every
transformation: ``jit(train_step)/transpose(jvp(attention_core))/dot``
is a backward op of attention, ``.../rematted_computation/.../mlp/...``
a recomputed forward op of the MLP.  A profiler trace's device ops joined
with the compiled program's HLO by instruction name thus give device time
per scope; where scopes nest, the innermost one owns the op.

Scopes change only that metadata, never the computation.  The benchmark
reads these names from here; a new scope is added to ``ALL``.
"""
from __future__ import annotations

import functools

import jax

#: q·k, the mask, the softmax and p·v, on every path that computes them
#: (plain, chunked, the XLA flash VJP and the Pallas kernel)
ATTENTION_CORE = "attention_core"
#: the rest of an attention block: q/k/v/o projections and RoPE
ATTENTION = "attention"
#: the dense SwiGLU feed-forward block
MLP = "mlp"
#: a mixture-of-experts feed-forward block: router, dispatch, experts
MOE = "moe"
#: a Mamba2 block outside its chunk recurrence
SSM = "ssm"
#: the SSD chunk recurrence (``_ssd_chunked`` or the Pallas kernel)
SSD_SCAN = "ssd_scan"
#: final norm, LM head and cross-entropy
LM_HEAD_LOSS = "lm_head_loss"
#: gradient clipping and the optimizer's update
OPTIMIZER = "optimizer"

ALL = (ATTENTION_CORE, ATTENTION, MLP, MOE, SSM, SSD_SCAN, LM_HEAD_LOSS,
       OPTIMIZER)


def scoped(name: str):
    """Decorator: trace every call of the function under
    ``jax.named_scope(name)``, a fresh scope object per call."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return inner
    return wrap
