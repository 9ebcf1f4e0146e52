"""codeqwen1.5-7b [dense] — qwen1.5-arch (GQA kv=4, q/k/v bias).
[hf:Qwen/CodeQwen1.5-7B; hf]

Grouped-query attention with 4 KV heads: the model card says
CodeQwen1.5 "includes group query attention (GQA)", and only 4 KV heads
give the published 7.25B parameters.  A layer holds 37,748,736
attention weights (q and o 4096 x 4096, k and v 4096 x 512), 5,120
q/k/v biases, 165,150,720 MLP weights (3 x 4096 x 13440) and 8,192
norm weights; 32 layers, an untied 92416 x 4096 embedding and head and
the final norm make 7,250,284,544.  With 32 KV heads it would be
8,190,038,016.  Qwen1.5 has biases on q, k and v only: none on the
output projection or in the MLP.
"""

from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="codeqwen1.5-7b",
    family="dense",
    num_layers=32,
    d_model=4096,
    num_heads=32,
    num_kv_heads=4,
    d_ff=13440,
    vocab_size=92416,
    norm="rmsnorm",
    activation="swiglu",
    use_bias=True,          # q/k/v biases only (mlp_bias stays False)
    rope_theta=1_000_000.0,
    source="hf:Qwen/CodeQwen1.5-7B",
)
