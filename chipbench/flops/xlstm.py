"""Model FLOPs of the xLSTM family, from the configuration's shapes only.

Counted: every matrix product of the forward pass (2 FLOPs per
multiply-add), and the mLSTM and sLSTM recurrences in their recurrent
form (per token and head: the outer product k v^T into the dh x dh
memory and its read q^T C; the sLSTM's recurrent h R); the backward pass
is twice the forward. Elementwise work and recomputation are not counted.
"""


def forward_flops_per_token(cfg: dict) -> float:
    d, heads, vocab = cfg["hidden_size"], cfg["num_heads"], cfg["vocab_size"]
    di = cfg["mlstm_proj_factor"] * d
    dh_m, dh_s = di // heads, d // heads
    mlstm = 2 * (d * 2 * di + 3 * di * di + 2 * di * heads + di * d) \
        + heads * 2 * (2 * dh_m * dh_m)
    slstm = 2 * (d * 4 * d + heads * dh_s * 4 * dh_s) \
        + 2 * 3 * d * cfg["slstm_ff"]
    per = {"mlstm": mlstm, "slstm": slstm}
    pattern = cfg["block_pattern"]
    layers = sum(per[pattern[i % len(pattern)]]
                 for i in range(cfg["num_hidden_layers"]))
    return float(layers + 2 * d * vocab)


def train_flops_per_token(cfg: dict, seq: int) -> float:
    del seq                      # the recurrent form does not grow with S
    return 3.0 * forward_flops_per_token(cfg)
