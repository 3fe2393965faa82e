"""Model FLOPs per trained token of a dense GQA transformer with a SwiGLU
feed-forward block and a tied unembedding (granite-8b).

6 FLOPs per matmul parameter per token (forward 2, backward 4), counting the
tied unembedding as a matmul and the embedding gather as none, plus causal
attention: per layer, forward QK^T and PV each take 2 * H * hd FLOPs per
visible key, a query at position t sees t + 1 keys, so a sequence of S
averages (S + 1) / 2 keys, and backward doubles the forward. Recomputation
under rematerialisation is not counted."""


def matmul_params(cfg: dict) -> int:
    """Parameters that enter a matmul per token: every layer's projections and
    feed-forward weights, plus the tied vocabulary projection."""
    d, hd = cfg["d_model"], cfg["d_model"] // cfg["num_heads"]
    attn = d * cfg["num_heads"] * hd * 2 + d * cfg["num_kv_heads"] * hd * 2
    ffn = 3 * d * cfg["d_ff"]
    return cfg["num_layers"] * (attn + ffn) + cfg["vocab_size"] * d


def attention_flops_per_token(cfg: dict, seq: int) -> float:
    """Causal attention FLOPs per token, forward and backward."""
    width = cfg["num_heads"] * (cfg["d_model"] // cfg["num_heads"])
    forward = 2 * 2 * width * (seq + 1) / 2
    return 3 * forward * cfg["num_layers"]


def train_flops_per_token(cfg: dict, seq: int) -> float:
    return 6.0 * matmul_params(cfg) + attention_flops_per_token(cfg, seq)
