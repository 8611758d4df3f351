"""Sizes and files of the benchmark's tiny cells, for its CPU tests."""
import dataclasses
import json
import pathlib
import sys


ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

TINY_TRAFFIC = {"rows": 2, "seq_len": 64, "mean_doc_len": 16,
                "min_doc_len": 4, "max_doc_factor": 4, "pareto_shape": 1.5,
                "markov_noise": 0.15, "length_seed": 11}
#: set from CPU readings at these sizes: the bf16 program reads at most
#: 4.2e-5 on the loss over 12 seeds and families, the fp8 control at least
#: 1.6e-4; the other numbers do not separate them at this size
TINY_LIMITS = {"loss": 1e-4, "first_grad": 0.05, "update": 0.1, "batch": 0}


def tiny_config(family: str):
    """A reduced program architecture and its configuration file."""
    from repro.configs.registry import get_arch

    training = json.loads((ROOT / "bench/configs/smollm-360m.json"
                           ).read_text())["training"]
    if family == "dense":
        arch = dataclasses.replace(get_arch("smollm-360m").reduced(),
                                   name="smollm-tiny")
        f = json.loads((ROOT / "bench/configs/smollm-360m.json").read_text())
        f.update(name="smollm-tiny", program_arch="smollm-tiny",
                 hidden_size=arch.d_model, intermediate_size=arch.d_ff,
                 num_attention_heads=arch.n_heads,
                 num_key_value_heads=arch.n_kv_heads,
                 head_dim=arch.head_dim_, num_hidden_layers=arch.n_layers,
                 vocab_size=arch.vocab_size, reference_block_tokens=64)
    else:
        arch = dataclasses.replace(get_arch("mamba2-370m").reduced(),
                                   name="mamba2-tiny")
        f = json.loads((ROOT / "bench/configs/mamba2-370m.json").read_text())
        s = arch.ssm
        f.update(name="mamba2-tiny", program_arch="mamba2-tiny",
                 d_model=arch.d_model, n_layer=arch.n_layers,
                 vocab_size=arch.vocab_size, d_state=s.d_state,
                 headdim=s.head_dim, expand=s.expand, ngroups=s.n_groups,
                 chunk_size=s.chunk, d_conv=s.d_conv,
                 reference_block_tokens=64)
    f["training"] = training
    return arch, f
