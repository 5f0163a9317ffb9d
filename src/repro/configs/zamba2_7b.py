"""zamba2-7b [hybrid] — Mamba-2 layers and two alternating weight-shared
attention blocks, the published Zamba2 block [arXiv:2411.15242; config
https://huggingface.co/Zyphra/Zamba2-7B-Instruct/blob/main/config.json].

81 layers, d_model 3584; Mamba-2: 112 heads of 64 in 2 groups, d_state 64,
conv 4 with a bias, chunk 256. The shared blocks (``num_mem_blocks`` 2,
alternating) run before layers 6, 11, 17, ..., 77 (13 applications); each
reads the concatenation of the residual stream and the embedding (7168
wide): 32 attention heads of 224, no GQA, rotary θ 1e4 over all 224 dims,
scale (224/2)^-1/2; a gated GELU (erf) MLP of 14336 with a rank-128 adapter
on its gate and up projections per application; no attention adapter; each
application's own output linear. Vocabulary 32,000, tied head. The block's
equations: ``models/families.build_zamba2``.
"""

from repro.core import Family, ModelConfig, SSMConfig, register

FULL = ModelConfig(
    arch_id="zamba2-7b",
    family=Family.HYBRID,
    n_layers=81,
    d_model=3584,
    n_heads=32,
    n_kv_heads=32,
    head_dim=224,
    d_ff=14336,
    vocab=32000,
    rope_theta=1e4,
    rms_eps=1e-5,
    tie_embeddings=True,
    ssm=SSMConfig(d_state=64, d_conv=4, expand=2, head_dim=64, n_groups=2,
                  chunk=256, conv_bias=True),
    hybrid_layer_ids=(6, 11, 17, 23, 29, 35, 41, 47, 53, 59, 65, 71, 77),
    num_mem_blocks=2,
    adapter_rank=128,
    source="https://huggingface.co/Zyphra/Zamba2-7B-Instruct",
)


def smoke() -> ModelConfig:
    """Four layers with applications before layers 1 and 3: both blocks,
    two groups."""
    import dataclasses
    return dataclasses.replace(
        FULL, n_layers=4, d_model=64, n_heads=4, n_kv_heads=4, head_dim=32,
        d_ff=128, vocab=512, hybrid_layer_ids=(1, 3), adapter_rank=8,
        ssm=SSMConfig(d_state=16, head_dim=16, expand=2, n_groups=2,
                      chunk=32, conv_bias=True))


register(FULL, smoke)
