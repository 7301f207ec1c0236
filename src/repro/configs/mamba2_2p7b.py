"""mamba2-2.7b — SSD (state-space duality) [arXiv:2405.21060].

hf ``state-spaces/mamba2-2.7b`` ``config.json``: 64 layers, d_model 2560,
attention-free, no MLP (``d_intermediate`` 0), vocab 50277 padded to a
multiple of 16: 50288 embedding rows, tied. Mamba2 module defaults:
d_state 128, expand 2 -> d_inner 5120, head_dim 64 -> 80 SSD heads, one
B/C group (``ngroups`` 1), conv width 4, chunk 256.
"""
from repro.configs.base import ArchConfig, SSMConfig

CONFIG = ArchConfig(
    name="mamba2-2.7b",
    family="ssm",
    source="hf state-spaces/mamba2-2.7b config.json; arXiv:2405.21060",
    num_layers=64,
    d_model=2560,
    num_heads=0,
    num_kv_heads=0,
    head_dim=64,
    d_ff=0,
    vocab_size=50288,
    ssm=SSMConfig(d_state=128, expand=2, head_dim=64, n_groups=1,
                  conv_width=4),
    norm="rmsnorm",
    tie_embeddings=True,
)
