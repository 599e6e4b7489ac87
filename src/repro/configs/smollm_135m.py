"""smollm-135m [dense] — the drafter of the SmolLM family, served with
smollm-360m as target (same tokenizer and vocabulary).
[hf:HuggingFaceTB/SmolLM-135M, config.json]"""

from repro.models.config import ModelConfig

CONFIG = ModelConfig(
    name="smollm-135m",
    family="dense",
    num_layers=30,
    d_model=576,
    num_heads=9,
    num_kv_heads=3,           # GQA
    head_dim=64,
    d_ff=1536,
    vocab_size=49_152,
)
