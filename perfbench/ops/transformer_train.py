"""Model FLOPs of one training token (PaLM appendix B): 6 per parameter
for the forward and backward products, plus the attention products
12·L·E·S, halved for a causal mask. Recomputed operations do not count.
"""


def flops_per_token(params: int, layers: int, embed: int, seq_len: int,
                    causal: bool = True) -> float:
    attn = 12.0 * layers * embed * seq_len
    return 6.0 * params + (attn / 2.0 if causal else attn)
