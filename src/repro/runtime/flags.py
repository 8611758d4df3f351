"""Runtime feature flags (thread-local, context-managed).

``use_pallas()`` switches the SSD mixer from its XLA chunked path to the
Pallas ``ssd_scan`` kernel (interpret-mode on CPU); the two are
numerically equivalent (tests assert it).  Attention has no flag:
``attention_core`` takes its Pallas kernels on a TPU by itself.
"""
from __future__ import annotations

import threading
from contextlib import contextmanager

_tls = threading.local()


def pallas_enabled() -> bool:
    return getattr(_tls, "pallas", False)


@contextmanager
def use_pallas(enabled: bool = True):
    prev = getattr(_tls, "pallas", False)
    _tls.pallas = enabled
    try:
        yield
    finally:
        _tls.pallas = prev


def attention_stubbed() -> bool:
    return getattr(_tls, "attn_stub", False)


@contextmanager
def stub_attention(enabled: bool = True):
    """Replace the attention contraction with a free pass-through — used to
    ATTRIBUTE which share of a lowering's cost is attention (diff of two
    dry-runs; benchmarks/kernel_roofline.py)."""
    prev = getattr(_tls, "attn_stub", False)
    _tls.attn_stub = enabled
    try:
        yield
    finally:
        _tls.attn_stub = prev
