"""mamba2-130m — attention-free SSM via state-space duality
[arXiv:2405.21060; https://huggingface.co/state-spaces/mamba2-130m].

config.json gives d_model 768, 24 layers, no MLP, vocabulary 50277 padded
to a multiple of 16 (50288 rows stored) and tied embeddings; the mixer
takes the Mamba2 layer's defaults (d_state 128, headdim 64, expand 2,
d_conv 4, chunk_size 256). Recalled, not checked against the file."""
from ..models.config import ArchConfig, SSMCfg

CONFIG = ArchConfig(
    name="mamba2-130m", family="ssm",
    num_layers=24, d_model=768, num_heads=1, num_kv_heads=1,
    d_ff=0,                            # mamba blocks have no FFN
    vocab_size=50288,                  # 50277 padded to a multiple of 16
    ssm=SSMCfg(d_state=128, head_dim=64, expand=2, d_conv=4, chunk=256),
    tie_embeddings=True,
)
