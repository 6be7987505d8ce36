"""Operations per token for the OPT family, from shapes alone: two
floating-point operations for every multiply-add of the projections,
the FFN, the head and attention (causal: a query at position t sees
t+1 keys). Layer norms, softmax, embeddings lookups are not counted;
neither is recompute."""
from cellbench.reference.opt import sizes


def forward_flops_per_token(cfg, context):
    """One token's forward pass with `context` keys in view."""
    s = sizes(cfg)
    d, f, v, layers = s["dim"], s["ffn"], s["vocab"], s["layers"]
    per_layer = 2 * (3 * d * d + d * d + 2 * d * f)   # qkv, proj, ffn
    attention = 2 * 2 * d * context                   # scores, values
    return layers * (per_layer + attention) + 2 * d * v


def train_flops_per_sample(cfg, traffic):
    """Forward once, backward twice, over one sequence of `seq_len`
    tokens; the mean causal context is (seq_len + 1) / 2."""
    t = int(traffic["seq_len"])
    return 3 * t * forward_flops_per_token(cfg, (t + 1) / 2.0)
