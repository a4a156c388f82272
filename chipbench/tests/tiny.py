"""Tiny configurations for the CPU tests: the program's own reduced
registry entries, stated as a configuration file would state them."""
import dataclasses

from repro.configs import get_arch, reduced_config


def tiny(name: str, *, dtype: str = "float32", k: int = 2, cf: float = 1.0,
         layers: int = 2):
    """(program config, configuration dict) of a reduced `name`: 8
    experts, top-`k`, capacity factor `cf`, so a prompt of 20 tokens
    overflows some experts' capacity."""
    cfg = reduced_config(get_arch(name))
    cfg = cfg.replace(dtype=dtype, num_layers=layers, moe=dataclasses.replace(
        cfg.moe, experts_per_token=k, capacity_factor=cf))
    c = {
        "name": f"tiny-{name}", "registry": name,
        "hidden_size": cfg.d_model, "intermediate_size": cfg.moe.d_expert,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim,
        "num_hidden_layers": cfg.num_layers,
        "num_experts": cfg.moe.num_experts,
        "num_experts_per_tok": cfg.moe.experts_per_token,
        "norm_topk_prob": True, "vocab_size": cfg.vocab_size,
        "rms_norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta,
        "tie_word_embeddings": cfg.tie_embeddings, "torch_dtype": dtype,
        "capacity_factor": cf,
    }
    return cfg, c
