"""A tiny configuration and traffic mix for CPU runs of the harness."""
from __future__ import annotations

import dataclasses

MODEL = {
    "registry": "bench-tiny", "architectures": ["LlamaForCausalLM"],
    "hidden_size": 64, "intermediate_size": 128,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "num_hidden_layers": 2, "vocab_size": 512, "rope_theta": 10000.0,
    "rms_norm_eps": 1e-06, "tie_word_embeddings": True, "hidden_act": "silu",
    "torch_dtype": "bfloat16", "chat_open_ids": [1, 2, 3],
}

MIX = {
    "loop": "open", "schedule_seed": 1, "rate_per_s": 12.0, "max_batch": 4, "prompt_pad": 32,
    "capacity": 48, "open_with": "chat_open_ids", "shared_prefix": {"count": 2, "tokens": 20,
                                      "zipf_s": 1.1},
    "prompt_tokens": [4, 12], "output_tokens": [2, 12],
    "check": {"tokens": 60, "max_requests": 6},
}


def register():
    """Put the tiny configuration in the program's registry."""
    from repro import configs
    configs._REGISTRY["bench-tiny"] = dataclasses.replace(
        configs.get_config("smollm-360m"), name="bench-tiny", n_layers=2,
        d_model=64, n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128,
        vocab_size=512)


def cell(mix=None, model=None, name="bench-tiny.chat"):
    from harness import spec
    e2e = [{"name": n, "unit": u} for n, u in (
        ("output_tok_per_s", "tokens/s"), ("ttft_p90_s", "s"),
        ("itl_p99_s", "s"), ("setup_s", "s"))]
    return spec.Cell(name, "bench-tiny", "chat", 1, model or MODEL,
                     mix or MIX, e2e, [])
