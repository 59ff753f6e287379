"""zamba2-7b [hybrid]: Zamba2-7B-Instruct, 81 Mamba2 layers (d_model 3584,
expand 2, 112 heads of 64, state 64, 2 groups, conv 4 with bias) and
``num_mem_blocks`` = 2 shared transformer blocks, used in turn before the
13 layers of ``hybrid_layer_ids``.  A block reads the 7168-wide concat of
the residual stream and the embedding output: 32 heads of 224 (MHA),
rotate-half rotary over all 224 channels, scale (224/2)^-0.5, and a gelu
MLP 14336 wide; each use has its own rank-128 LoRA on ``gate_up`` and its
own 3584x3584 linear whose output joins the next Mamba layer's input only.
Norms are RMS with eps 1e-5, the head is tied to the embedding.
[https://huggingface.co/Zyphra/Zamba2-7B-Instruct/blob/main/config.json;
equations as in transformers' ``modeling_zamba2``]"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="zamba2-7b",
    family="hybrid",
    n_layers=81,
    d_model=3584,
    n_heads=32,
    n_kv_heads=32,
    head_dim=224,
    d_ff=14336,
    vocab_size=32000,
    ssm_state=64,
    ssm_expand=2,
    ssm_head_dim=64,
    ssm_ngroups=2,
    ssm_conv=4,
    ssm_conv_bias=True,
    hybrid_layer_ids=(6, 11, 17, 23, 29, 35, 41, 47, 53, 59, 65, 71, 77),
    num_mem_blocks=2,
    adapter_rank=128,
    rope="rope_half",
    rope_theta=10000.0,
    activation="gelu",
    norm_eps=1e-5,
    tie_embeddings=True,
)
