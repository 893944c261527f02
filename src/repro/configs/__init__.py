"""Config registry: 10 assigned architectures + the paper's GPT2 family.

``get_config(name)`` returns the full-scale config; ``get_smoke_config(name)``
returns a reduced same-family config for CPU smoke tests (small widths, few
experts, tiny vocab).  Full configs are exercised only via the dry-run
(ShapeDtypeStruct — no allocation).
"""
from __future__ import annotations

import dataclasses

from repro.configs.base import (ModelConfig, MoEConfig, SSMConfig, ShapeConfig,
                                SHAPES)

ARCH_MODULES = {
    "gemma2-9b": "gemma2_9b",
    "gemma3-12b": "gemma3_12b",
    "yi-34b": "yi_34b",
    "starcoder2-3b": "starcoder2_3b",
    "jamba-v0.1-52b": "jamba_v01_52b",
    "whisper-base": "whisper_base",
    "rwkv6-7b": "rwkv6_7b",
    "qwen2-vl-2b": "qwen2_vl_2b",
    "moonshot-v1-16b-a3b": "moonshot_v1_16b_a3b",
    "deepseek-moe-16b": "deepseek_moe_16b",
}

ASSIGNED_ARCHS = tuple(ARCH_MODULES)

# long_500k applicability (DESIGN.md §4): sub-quadratic / local-attention
# archs run it; pure full-attention archs (and whisper) skip it.
LONG_CONTEXT_ARCHS = ("rwkv6-7b", "jamba-v0.1-52b", "gemma2-9b", "gemma3-12b")

# Checkpoint policy to use WHEN remat is enabled, measured on the 8-fake-
# device host mesh (`benchmarks/run.py --only remat`, smoke shapes, median
# dots-vs-nothing step-time ratio over 3 runs): 'dots' (save matmul outputs
# with no batch dims) only where it beat full recompute by >=5%; washes and
# losses keep 'nothing' — full recompute also has the lowest live memory,
# which is why remat is on in the first place.  Whisper's encoder-decoder
# path takes a plain jax.checkpoint either way.
REMAT_DEFAULTS = {
    "gemma2-9b": "nothing",            # 1.00x median (noisy, no stable win)
    "gemma3-12b": "dots",              # 1.11x
    "yi-34b": "dots",                  # 1.07x
    "starcoder2-3b": "nothing",        # 0.92x
    "jamba-v0.1-52b": "nothing",       # 1.02x (wash)
    "whisper-base": "nothing",         # policy label is a no-op (encdec)
    "rwkv6-7b": "nothing",             # 0.94x (scan recompute is elementwise)
    "qwen2-vl-2b": "dots",             # 1.15x
    "moonshot-v1-16b-a3b": "nothing",  # 0.82x
    "deepseek-moe-16b": "dots",        # 1.08x
}


def default_remat(name: str) -> str:
    """Measured checkpoint policy for ``name`` when remat is enabled
    (see REMAT_DEFAULTS); unmeasured configs take 'nothing'."""
    return REMAT_DEFAULTS.get(name, "nothing")


def get_config(name: str) -> ModelConfig:
    if name.startswith("gpt2"):
        from repro.configs.gpt2 import gpt2
        layers = int(name.split("-")[1][:-1]) if "-" in name else 12
        return gpt2(layers)
    from repro.configs.paper_testbeds import PAPER_TESTBEDS
    if name in PAPER_TESTBEDS:
        return PAPER_TESTBEDS[name]
    import importlib
    mod = importlib.import_module(f"repro.configs.{ARCH_MODULES[name]}")
    return mod.CONFIG


def applicable_shapes(name: str) -> list:
    """Shape cells for this arch; long_500k only for sub-quadratic archs."""
    out = []
    for shape in SHAPES.values():
        if shape.name == "long_500k" and name not in LONG_CONTEXT_ARCHS:
            continue
        out.append(shape)
    return out


def get_smoke_config(name: str) -> ModelConfig:
    """Reduced same-family config: 1-2 pattern periods deep, narrow, tiny
    vocab — runs a forward/train step on CPU in seconds."""
    cfg = get_config(name)
    moe = cfg.moe
    if moe is not None:
        moe = dataclasses.replace(moe, num_experts=8,
                                  top_k=min(moe.top_k, 2),
                                  num_shared_experts=min(moe.num_shared_experts, 1),
                                  expert_ffn_dim=32 if moe.expert_ffn_dim else 0)
    ssm = cfg.ssm
    if ssm is not None:
        ssm = dataclasses.replace(ssm, d_state=4,
                                  head_dim=16 if ssm.kind == "rwkv6" else ssm.head_dim)
    heads = min(cfg.num_heads, 4)
    kv = min(cfg.num_kv_heads, heads)
    while heads % kv:
        kv -= 1
    period = cfg.pattern_period
    window = tuple(min(w, 8) for w in cfg.window_pattern)
    return dataclasses.replace(
        cfg,
        name=cfg.name + "-smoke",
        num_layers=2 * period if period <= 4 else period,
        d_model=64, num_heads=heads, num_kv_heads=kv, head_dim=16,
        d_ff=128, vocab_size=256,
        moe=moe, ssm=ssm, window_pattern=window,
        num_encoder_layers=2 if cfg.is_encoder_decoder else 0,
        encoder_seq_len=16 if cfg.is_encoder_decoder else cfg.encoder_seq_len,
        num_frontend_embeds=8 if cfg.frontend != "none" else 0,
        max_seq_len=128,
    )
