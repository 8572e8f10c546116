"""The 10 registered LM architectures and the agent-sim architectures.

Sources are noted per config; where a one-line description conflicts with
the published model card we follow the card and note the deviation in the
config's ``notes``.
"""
from __future__ import annotations

from repro.configs.base import (MLAConfig, ModelConfig, MoEConfig,
                                RopeScaling, SimArch, SSMConfig, register,
                                register_sim)

# --- deepseek-v2-lite-16b [arXiv:2405.04434; hf] ---------------------------
# 27L d=2048, 16 heads, MLA kv_lora=512, MoE: 64 routed top-6 + 2 shared,
# expert_ff=1408, first layer dense (dense_ff=10944); YaRN rope scaling
# (factor 40 over 4096), softmax routing without top-k renormalisation,
# rms_norm_eps 1e-6 (every RMSNorm's default).
register(ModelConfig(
    name="deepseek-v2-lite-16b", family="moe",
    num_layers=27, d_model=2048, num_q_heads=16, num_kv_heads=16,
    d_ff=10944, vocab_size=102400,
    attention_kind="mla",
    mla=MLAConfig(kv_lora_rank=512, qk_nope_dim=128, qk_rope_dim=64,
                  v_head_dim=128),
    rope_scaling=RopeScaling(factor=40.0, original_max_position=4096,
                             beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                             mscale_all_dim=0.707),
    max_position=163840,
    moe=MoEConfig(num_experts=64, top_k=6, expert_ff=1408, num_shared=2,
                  first_k_dense=1, dense_ff=10944, norm_topk_prob=False),
    activation="silu", norm="rms",
    notes="MLA + fine-grained MoE; a description listing '160 routed' "
          "matches deepseek-v2 (236B), not -lite; we follow the hf card "
          "(64 routed). The rope channels of q and k_rope pair split-half "
          "(feature i with i + 32); the hf code de-interleaves them first, "
          "a fixed permutation of those 64 weight columns.",
))

# --- kimi-k2-1t-a32b [arXiv: Kimi K2 tech report; paper-table] --------------
# 61L d=7168, 64 heads (GQA kv=8 per brief), MoE 384 experts top-8,
# expert_ff=2048, 1 shared expert, first layer dense.
register(ModelConfig(
    name="kimi-k2-1t-a32b", family="moe",
    num_layers=61, d_model=7168, num_q_heads=64, num_kv_heads=8,
    head_dim=128, d_ff=18432, vocab_size=163840,
    attention_kind="gqa", rope_base=50000.0,
    moe=MoEConfig(num_experts=384, top_k=8, expert_ff=2048, num_shared=1,
                  first_k_dense=1, dense_ff=18432, capacity_factor=1.25),
    activation="silu", norm="rms",
    notes="Brief specifies GQA kv=8 (the release uses MLA); we follow the "
          "brief. 1.03e12 params, ~32B active.",
))

# --- gemma2-27b [arXiv:2408.00118; hf] --------------------------------------
# 46L d=4608, 32 heads / 16 kv, head_dim 128, GeGLU d_ff=36864 (gate+up),
# alternating local(4096)/global attention, attn softcap 50, final softcap 30,
# query_pre_attn_scalar=144, RMSNorm(+1) pre+post, tied + scaled embeddings.
register(ModelConfig(
    name="gemma2-27b", family="dense",
    num_layers=46, d_model=4608, num_q_heads=32, num_kv_heads=16,
    head_dim=128, d_ff=36864, vocab_size=256000,
    window=4096, window_pattern="alternating",
    attn_softcap=50.0, final_softcap=30.0, query_scale=144.0,
    activation="gelu_tanh", norm="rms_offset",
    tie_embeddings=True, scale_embeddings=True,
))

# --- stablelm-3b [hf:stabilityai/stablelm-*] --------------------------------
# 32L d=2560, 32 heads MHA, d_ff=6912, vocab 50304, partial rotary 25%.
register(ModelConfig(
    name="stablelm-3b", family="dense",
    num_layers=32, d_model=2560, num_q_heads=32, num_kv_heads=32,
    d_ff=6912, vocab_size=50304,
    rope_fraction=0.25, norm="layer", attn_bias=False,
    activation="silu",
))

# --- phi4-mini-3.8b [arXiv:2412.08905; hf] ----------------------------------
# 32L d=3072, 24 heads / 8 kv, SwiGLU d_ff=8192, vocab 200064, tied embeds.
register(ModelConfig(
    name="phi4-mini-3.8b", family="dense",
    num_layers=32, d_model=3072, num_q_heads=24, num_kv_heads=8,
    head_dim=128, d_ff=8192, vocab_size=200064,
    activation="silu", norm="rms", tie_embeddings=True,
))

# --- granite-20b [arXiv:2405.04324; hf] -------------------------------------
# GPT-BigCode style: 52L d=6144, 48 heads MQA (kv=1), d_ff=24576, learned
# absolute positions, LayerNorm + gelu, biases.
register(ModelConfig(
    name="granite-20b", family="dense",
    num_layers=52, d_model=6144, num_q_heads=48, num_kv_heads=1,
    head_dim=128, d_ff=24576, vocab_size=49152,
    pos_enc="absolute", learned_positions=True, max_position=32768 + 8192,
    mlp_kind="plain", activation="gelu_tanh", norm="layer", attn_bias=True,
    notes="MQA; absolute learned positions exercise the paper's 'absolute' "
          "baseline row at LM scale.",
))

# --- internvl2-26b [arXiv:2404.16821; hf] -----------------------------------
# InternLM2-20B backbone: 48L d=6144, 48 heads / 8 kv, d_ff=16384, SwiGLU.
# InternViT frontend is a STUB: input_specs provides patch embeddings
# (vision_prefix tokens of width d_model).
register(ModelConfig(
    name="internvl2-26b", family="vlm",
    num_layers=48, d_model=6144, num_q_heads=48, num_kv_heads=8,
    head_dim=128, d_ff=16384, vocab_size=92553,
    activation="silu", norm="rms",
    vision_prefix=256,
    notes="Backbone only; InternViT-6B patch embeddings arrive precomputed "
          "as a 256-token prefix.",
))

# --- hymba-1.5b [arXiv:2411.13676; hf] --------------------------------------
# 32L d=1600, 25 q heads / 5 kv (head_dim 64), d_ff=5504, parallel
# attention+mamba heads, SWA except first/middle/last global layers.
register(ModelConfig(
    name="hymba-1.5b", family="hybrid",
    num_layers=32, d_model=1600, num_q_heads=25, num_kv_heads=5,
    head_dim=64, d_ff=5504, vocab_size=32001,
    window=1024, window_pattern="mostly_local", parallel_ssm=True,
    ssm=SSMConfig(kind="mamba", state_size=16, d_inner=3200, chunk=128),
    activation="silu", norm="rms",
    long_context_ok=True,
    notes="Parallel attn+SSM heads; meta-tokens omitted. "
          "SWA + SSM make long_500k decode sub-quadratic.",
))

# --- whisper-base [arXiv:2212.04356] ----------------------------------------
# enc-dec, 6L each, d=512, 8 heads, d_ff=2048; conv frontend stubbed (inputs
# are 1500 precomputed frame embeddings).
register(ModelConfig(
    name="whisper-base", family="audio",
    num_layers=6, d_model=512, num_q_heads=8, num_kv_heads=8,
    head_dim=64, d_ff=2048, vocab_size=51865,
    enc_dec=True, encoder_layers=6, encoder_frames=1500,
    pos_enc="absolute", learned_positions=True, max_position=32768 + 256,
    mlp_kind="plain", activation="gelu", norm="layer", attn_bias=True,
    notes="Decoder max length far beyond the real 448-token budget so the "
          "assigned decode_32k/long shapes remain well-defined.",
))

# --- rwkv6-7b [arXiv:2404.05892; hf] ----------------------------------------
# Finch: 32L d=4096, attention-free (WKV6, head 64), channel-mix d_ff=14336.
register(ModelConfig(
    name="rwkv6-7b", family="ssm",
    num_layers=32, d_model=4096, num_q_heads=64, num_kv_heads=64,
    d_ff=14336, vocab_size=65536,
    attention_kind="none", pos_enc="none", mlp_kind="rwkv",
    ssm=SSMConfig(kind="rwkv6", head_dim=64, chunk=16),
    norm="layer",
    long_context_ok=True,
    notes="Paper's attention technique inapplicable (attention-free).",
))

# --- agent-sim architectures (paper Table I rows) ---------------------------
# One arch per attention mechanism, identical everywhere else, so trained
# comparisons isolate the encoding (the paper's invariant-vs-absolute
# claim). ``.reduced()`` gives the CPU-sized variant the train_sim launcher,
# the train bench, and CI smoke jobs use; the full shapes are what
# ``launch.dryrun`` lowers on the production mesh alongside the LM arches.
_SIM_NOTES = {
    "absolute": "non-invariant baseline: learned Fourier pose embedding "
                "added to token features",
    "rope2d": "translation-invariant only (paper Sec. II-D)",
    "se2_repr": "exact SE(2) invariance via homogeneous-matrix "
                "representation (Sec. II-E)",
    "se2_fourier": "the paper's linear-memory SE(2) encoding (Sec. III)",
}
for _enc, _note in _SIM_NOTES.items():
    register_sim(SimArch(name=f"sim-{_enc.replace('_', '-')}",
                         encoding=_enc, notes=_note))
