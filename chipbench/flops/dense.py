"""Model FLOPs of the dense family, from the configuration's shapes only.

Counted: every matrix product of the forward pass at 2 FLOPs per
multiply-add (the q, k, v and output projections, the gated MLP's three,
the output head), and attention's two products (scores and the weighted
sum of values) over the keys each query may see: causal, and within the
sliding window where there is one. The keys the program masks are not
useful work. The backward pass is twice the forward; recomputation, the
embedding lookup and elementwise work are not counted.
"""


def mean_keys_seen(seq: int, window) -> float:
    """Mean over the positions of a sequence of the keys a query sees."""
    w = window or seq
    return sum(min(t + 1, w) for t in range(seq)) / seq


def forward_flops_per_token(cfg: dict, seq: int) -> float:
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    kv, ff = cfg["num_key_value_heads"], cfg["intermediate_size"]
    hd = cfg.get("head_dim", d // heads)
    proj = 2 * d * heads * hd + 2 * 2 * d * kv * hd + 2 * heads * hd * d
    mlp = 3 * 2 * d * ff
    attn = 2 * 2 * heads * hd * mean_keys_seen(seq, cfg.get("sliding_window"))
    layer = proj + mlp + attn
    return float(cfg["num_hidden_layers"] * layer + 2 * d * cfg["vocab_size"])


def train_flops_per_token(cfg: dict, seq: int) -> float:
    return 3.0 * forward_flops_per_token(cfg, seq)
