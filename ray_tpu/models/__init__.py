from ray_tpu.models.transformer import (TransformerConfig, TransformerLM,
                                        count_params, init_cache)

MODEL_REGISTRY = {
    "llama-debug": TransformerConfig(
        vocab_size=1024, d_model=256, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=512, max_seq_len=512),
    "llama-125m": TransformerConfig(
        vocab_size=32000, d_model=768, n_layers=12, n_heads=12, n_kv_heads=12,
        d_ff=2048, max_seq_len=2048),
    "llama-350m": TransformerConfig(
        vocab_size=32000, d_model=1024, n_layers=24, n_heads=16,
        n_kv_heads=16, d_ff=2816, max_seq_len=2048),
    "llama-1b": TransformerConfig(
        vocab_size=32000, d_model=2048, n_layers=16, n_heads=32, n_kv_heads=8,
        d_ff=5632, max_seq_len=4096),
    "llama-7b": TransformerConfig(
        vocab_size=32000, d_model=4096, n_layers=32, n_heads=32, n_kv_heads=32,
        d_ff=11008, max_seq_len=4096),
    # TPU-native flagship geometry: 128-lane heads (head_dim=128) fill the
    # MXU's 128-wide systolic tiles; the classic hd=64 llama layout leaves
    # half the array idle on QK^T/PV. An ablation from before PR 21 read
    # 42.8% against 32.1% MFU for the same 350m FLOPs (its record went
    # with the old measuring kit, PR 29; not measured by perfbench/)
    "tpu-125m": TransformerConfig(
        vocab_size=32000, d_model=768, n_layers=12, n_heads=6, n_kv_heads=6,
        d_ff=2048, max_seq_len=2048),
    "tpu-350m": TransformerConfig(
        vocab_size=32000, d_model=1024, n_layers=24, n_heads=8, n_kv_heads=8,
        d_ff=2816, max_seq_len=2048),
    "tpu-1b": TransformerConfig(
        vocab_size=32000, d_model=2048, n_layers=16, n_heads=16,
        n_kv_heads=16, d_ff=5632, max_seq_len=4096),
    # Larger rungs keep hd=128 and add GQA (4:1) — KV projections are
    # bandwidth, not FLOPs, and 8 KV heads shard cleanly over an 8-way
    # tensor axis. tpu-3b is the largest single-v5e-chip (16 GB) rung:
    # it needs bf16 params + adafactor + chunked cross-entropy to fit;
    # tpu-7b (llama-7b-class FLOPs, MXU-aligned d_ff) is the multi-chip
    # FSDP flagship.
    "tpu-3b": TransformerConfig(
        vocab_size=32000, d_model=3072, n_layers=24, n_heads=24,
        n_kv_heads=8, d_ff=8192, max_seq_len=4096),
    "tpu-7b": TransformerConfig(
        vocab_size=32000, d_model=4096, n_layers=32, n_heads=32,
        n_kv_heads=8, d_ff=11264, max_seq_len=4096),
    # MoE family (models/moe.py): expert-parallel over the mesh `expert` axis
    "moe-debug": TransformerConfig(
        vocab_size=1024, d_model=256, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=512, max_seq_len=512, n_experts=4, expert_top_k=2),
    "mixtral-8x7b": TransformerConfig(
        vocab_size=32000, d_model=4096, n_layers=32, n_heads=32, n_kv_heads=8,
        d_ff=14336, max_seq_len=4096, n_experts=8, expert_top_k=2),
}

from ray_tpu.models.generate import make_generate_fn
from ray_tpu.models.sampling import sample_logits, sample_logits_dynamic

__all__ = ["TransformerConfig", "TransformerLM", "MODEL_REGISTRY",
           "count_params", "init_cache", "make_generate_fn",
           "sample_logits", "sample_logits_dynamic"]
