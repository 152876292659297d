"""Operations and bytes the paged GQA decode read needs for one call
(every layer of one decode step): what the algorithm must touch, not the
pages the kernel walks.

For each active slot and layer, the query of ``n_heads`` heads reads the
``ctx`` live keys and values of its ``n_kv_heads`` groups (position 0 up
to and including the token written this step) and writes one output per
head:

    flops = layers * sum(ctx) * n_heads * head_dim * 4     (QK^T and PV)
    bytes = layers * (sum(ctx) * n_kv_heads * head_dim * 2 * kv_bytes
                      + n_active * n_heads * head_dim * 2 * act_bytes)
"""


def cost(*, ctx_tokens, n_active, n_layers, n_heads, n_kv_heads, head_dim,
         kv_bytes=2, act_bytes=2):
    flops = n_layers * ctx_tokens * n_heads * head_dim * 4
    nbytes = n_layers * (ctx_tokens * n_kv_heads * head_dim * 2 * kv_bytes
                         + n_active * n_heads * head_dim * 2 * act_bytes)
    return float(flops), float(nbytes)
