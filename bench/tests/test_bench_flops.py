"""Each configuration's FLOP function against ``core.archcount``'s closed
form: three times the forward pass's matrix FLOPs (forward and backward,
no recomputation)."""
import json

import pytest

from bench import spec
from bench_tiny import ROOT, tiny_config

CONFIGS = {"smollm-360m": "dense", "mamba2-370m": "ssm"}


def _archcount_flops_per_token(arch, seq):
    from repro.core import archcount, properties

    pv = archcount.forward_counts(arch)
    mxu = pv[properties.mxu_key(16)]
    return 3 * mxu.eval({"B": 1, "S": seq}) / seq


@pytest.mark.parametrize("seq", [64, 2048, 14336])
@pytest.mark.parametrize("name", sorted(CONFIGS))
@pytest.mark.parametrize("size", ["tiny", "full"])
def test_flops_match_archcount(name, size, seq):
    from repro.configs.registry import get_arch

    family = CONFIGS[name]
    if size == "tiny":
        arch, config = tiny_config(family)
    else:
        arch = get_arch(name)
        config = json.loads((ROOT / f"bench/configs/{name}.json").read_text())
    ref = spec.load_module(ROOT / f"bench/models/{family}.py", f"t_{family}")
    ours = ref.flops_per_token(config, seq)
    assert ours == pytest.approx(_archcount_flops_per_token(arch, seq),
                                 rel=1e-12)


def test_full_size_flops_per_token():
    """The counts PERF.md quotes, at 2048 positions."""
    for name, family, want in (("smollm-360m", "dense", 2.548e9),
                               ("mamba2-370m", "ssm", 2.586e9)):
        config = json.loads((ROOT / f"bench/configs/{name}.json").read_text())
        ref = spec.load_module(ROOT / f"bench/models/{family}.py",
                               f"f_{family}")
        assert ref.flops_per_token(config, 2048) == pytest.approx(want,
                                                                  rel=1e-3)


@pytest.mark.parametrize("seq", [64, 2047, 2048, 14336])
@pytest.mark.parametrize("size", ["tiny", "full"])
def test_dense_flops_are_the_attention_term_and_the_rest(size, seq):
    """Naming the attention term left ``flops_per_token`` bit for bit the
    single sum it was (kept here as written before)."""
    if size == "tiny":
        _, c = tiny_config("dense")
    else:
        c = json.loads((ROOT / "bench/configs/smollm-360m.json").read_text())
    dense = spec.load_module(ROOT / "bench/models/dense.py", "b_dense")
    d, ff, V, L = (c["hidden_size"], c["intermediate_size"], c["vocab_size"],
                   c["num_hidden_layers"])
    H, KV, dh = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    proj = d * H * dh + 2 * d * KV * dh + H * dh * d
    attn = 2 * H * dh * (seq / 2)
    before = 6.0 * (L * (proj + 3 * d * ff + attn) + d * V)
    assert dense.flops_per_token(c, seq) == before
    assert dense.attention_flops_per_token(c, seq) == 6.0 * L * attn
