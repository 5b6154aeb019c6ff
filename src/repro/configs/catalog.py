"""Import every architecture config so the registry is populated."""
from . import (  # noqa: F401
    llama4_scout_17b_a16e, mixtral_8x22b, command_r_35b, gemma3_4b,
    starcoder2_15b, olmo_1b, mamba2_130m, jamba_v0_1_52b, qwen2_vl_2b,
    whisper_tiny, bert_base, bert_large, gpt2_large, moonlight_16b_a3b,
)

ASSIGNED = [
    "llama4-scout-17b-a16e", "mixtral-8x22b", "command-r-35b", "gemma3-4b",
    "starcoder2-15b", "olmo-1b", "mamba2-130m", "jamba-v0.1-52b",
    "qwen2-vl-2b", "whisper-tiny",
]
PAPER_OWN = ["bert-base", "bert-large", "gpt2-large"]
